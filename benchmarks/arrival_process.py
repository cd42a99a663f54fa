"""Arrival-process serving benchmark: Poisson arrivals, ragged
continuation lengths, lockstep-FIFO vs ragged-FIFO vs ragged-EDF.

Throughput benchmarks (``batched_invoke``, ``ragged_invoke``) measure
the cost of one dispatch; this one measures what a *user* feels —
completion latency under a live arrival process — and what an operator
sells — SLO attainment.  Requests arrive by a deterministic-seed
Poisson process with ragged continuation lengths (1..6 frames) and a
per-request deadline; three disciplines serve the identical workload:

  * ``lockstep_fifo`` — an ``InterpreterPool`` wave admits up to B
    queued requests FIFO and must run ALL of them to the LONGEST
    request's length before admitting again (a lockstep pool cannot
    retire a lane mid-wave): the head-of-line blocking baseline;
  * ``ragged_fifo``  — the real ``MultiTenantHost`` micro scheduler
    (``micro_step``): lanes admit/retire between waves, FIFO order;
  * ``ragged_edf``   — same host, ``EDFPolicy``: the free lane goes to
    the queued request whose deadline expires soonest.

Dispatches are REAL (the actual compiled programs run every tick);
latency is accounted on a **virtual clock** that advances by the warm
measured cost of one dispatch per tick, and the host's scheduling
policies read that same clock — so the reported p50/p95/p99 completion
latencies and SLO attainment are deterministic given the seed, up to
the single measured dispatch constant.  A second section reports the
bucketed-prefill compile counts (``ServingEngine.prefill_compiles``)
for mixed prompt lengths, bucketed vs exact-length.

Emits ``BENCH_arrival_process.json`` via ``python -m benchmarks.run
arrival_process``; ``python -m benchmarks.arrival_process --tiny``
runs a seconds-scale end-to-end smoke (no JSON written) used by the
slow test tier.  How to read the rows: docs/SCHEDULING.md.

``--preempt`` runs the PREEMPTION benchmark instead (also registered
as ``preemption`` in ``benchmarks.run`` → ``BENCH_preemption.json``):
a heavy-tail mix — a few 6-frame best-effort monopolizers among
1-frame deadline-class requests — served with PR-3 EDF admission alone
vs EDF + EDF-displace preemption over checkpointable lanes, plus the
pod-engine analogue where a long-prompt monopolizer is tamed by
preemption + chunked prefill.  The chunked engine's configuration
comes from the calibration-profile CACHE
(``benchmarks/results/profiles/``, via ``ServingEngine.from_profile``)
rather than hand constants — calibrated once and persisted on the
first full run.  How to read those rows: docs/PREEMPTION.md.

``--paged`` runs the PAGED-KV occupancy benchmark (registered as
``paged_kv`` → ``BENCH_paged_kv.json``): the identical short-request
flood served by a contiguous engine (whole cache_len KV slabs, slots
bounded by HBM) and a paged engine given the SAME HBM budget as a
shared block pool — admissible concurrency is bounded by blocks
actually needed, not worst-case slabs, and the decoded tokens must
stay bit-identical.  How to read those rows: docs/ARCHITECTURE.md §8.

``--replicas`` runs the REPLICA-ROUTING sweep (registered as
``replica_sweep`` → ``BENCH_replica_sweep.json``): the PR-4 engine
arrival mix (short deadline class + long monopolizers) served by 1, 2
and 4 ``ServingEngine`` replicas behind a ``ReplicaRouter``, swept
over a routing-policy LADDER: load-blind round-robin and load-aware
least-loaded route at admission time only, while locality adds the
router's stickiness-aware work-stealing rebalancer (the rebalancer is
the locality mechanism — it moves only checkpoint-free work, so it
needs ``home_of`` bookkeeping to be safe).  Dispatches are real; the
virtual clock advances by the MAX of the
replicas' per-tick measured costs (replicas run in parallel on
disjoint device sets), so throughput and p99 vs replica count are
deterministic given the seed — and every config's decoded tokens must
be bit-identical to the single-replica baseline (routing is
placement, never semantics).  How to read those rows:
docs/ARCHITECTURE.md §9.

``--stream`` runs the STREAMING benchmark (registered as ``streaming``
→ ``BENCH_streaming.json``): time-to-first-token and
inter-token-latency percentiles from per-token ``StreamEvent``
timestamps, synchronous vs OVERLAPPED decode (``overlap=True``:
readback deferred one step, docs/STREAMING.md), swept over the family
matrix on the virtual clock — a sync tick costs ``decode + host``
(the device step, then the blocking readback + sampling), an overlap
tick ``max(decode, host)`` (the host settles step i-1 while step i
computes) — plus a WALL-CLOCK section on the dense flagship that
serves the same saturated workload on real time and reports the
observed ITL ratio next to the cost model's prediction, validating
the virtual model against real dispatch overlap.  Tokens must stay
bit-identical between the modes in every row (asserted, and again by
the family-parity ``streaming`` column).  How to read those rows:
docs/STREAMING.md.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.apps import build_fc_stack
from repro.apps.models import representative_dataset
from repro.core import (AllOpsResolver, InterpreterPool, MicroModel,
                        RaggedInterpreterPool, export)
from repro.serving import MultiTenantHost, get_policy

from .common import print_table, save_result, time_call

SEED = 0
LANES = 16
N_REQUESTS = 160
OCCUPANCIES = (0.25, 0.5, 0.75, 0.9)
FRAME_LO, FRAME_HI = 1, 6          # frames per request, inclusive
SLO_FACTOR = 4.0                   # deadline = arrival + frames*D*factor
IN_SHAPE = (1, 64)                 # fc_stack input

# --preempt section: heavy-tail mix over few lanes (monopolization)
PREEMPT_LANES = 4
PREEMPT_N = 120
MONO_FRAC = 0.25                   # 6-frame best-effort monopolizers
PREEMPT_OCC = 0.75
TIGHT_SLO_TICKS = 3.0              # deadline-class: arrival + 3 ticks


class VirtualClock:
    """The benchmark's µs clock: a mutable ``now_us`` the simulation
    advances by one measured dispatch cost per tick.  Passed as the
    host's ``clock`` so admission policies (EDF deadlines, aging) run
    on simulated time — deterministic latency accounting over real
    dispatches."""

    def __init__(self) -> None:
        self.now_us = 0.0

    def __call__(self) -> int:
        return int(self.now_us)


def _build_model() -> MicroModel:
    gb = build_fc_stack()
    return MicroModel(export(
        gb, representative_dataset=representative_dataset(gb),
        quantize_int8=True))


def _workload(rng: np.random.Generator, n: int, lanes: int,
              occupancy: float, dispatch_us: float) -> Dict[str, np.ndarray]:
    """Poisson arrivals sized so offered load = ``occupancy`` of the
    pool's service capacity, ragged frame counts, per-request inputs
    and deadlines.  Deterministic for a given seed."""
    frames = rng.integers(FRAME_LO, FRAME_HI + 1, n)
    mean_frames = (FRAME_LO + FRAME_HI) / 2
    rate = occupancy * lanes / (mean_frames * dispatch_us)  # req per µs
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    deadlines = arrivals + frames * dispatch_us * SLO_FACTOR
    inputs = [[rng.normal(0, 1, IN_SHAPE).astype(np.float32)
               for _ in range(k)] for k in frames]
    return {"frames": frames, "arrivals": arrivals,
            "deadlines": deadlines, "inputs": inputs}


# ---------------------------------------------------------------------------
# the three disciplines (identical workload in, completion times out)
# ---------------------------------------------------------------------------

def _sim_ragged(model, resolver, wl, lanes: int, dispatch_us: float,
                policy_name: str) -> np.ndarray:
    """Drive the REAL MultiTenantHost micro scheduler tick by tick on
    the virtual clock; returns per-request completion times (µs)."""
    clock = VirtualClock()
    host = MultiTenantHost(arena_bytes=64 << 20,
                           policy=get_policy(policy_name), clock=clock)
    host.add_ragged_micro("m", model, resolver, lanes=lanes)
    n = len(wl["arrivals"])
    done_at = np.full(n, np.nan)
    nxt = 0
    while True:
        while nxt < n and wl["arrivals"][nxt] <= clock.now_us:
            host.submit_micro(
                "m", nxt, [[x] for x in wl["inputs"][nxt]],
                deadline_us=int(wl["deadlines"][nxt]),
                arrival_us=int(wl["arrivals"][nxt]))
            nxt += 1
        if not host._micro_pending():
            if nxt >= n:
                break
            clock.now_us = wl["arrivals"][nxt]   # idle: jump to arrival
            continue
        host.micro_step()
        clock.now_us += dispatch_us
        for uid, res in host.micro_results["m"].items():
            if res.done and np.isnan(done_at[uid]):
                done_at[uid] = clock.now_us
    return done_at


def _sim_lockstep(model, resolver, wl, lanes: int,
                  dispatch_us: float) -> np.ndarray:
    """FIFO lockstep baseline: admit up to ``lanes`` queued requests,
    run the whole wave to the longest request's frame count (idle lanes
    re-dispatch — a lockstep pool cannot retire them), then admit the
    next wave.  A request completes when its own last frame runs; the
    *wave* still blocks admission until the longest one finishes."""
    pool = InterpreterPool(model, resolver, batch=lanes)
    n = len(wl["arrivals"])
    done_at = np.full(n, np.nan)
    queue: List[int] = []
    t, nxt = 0.0, 0
    while nxt < n or queue:
        while nxt < n and wl["arrivals"][nxt] <= t:
            queue.append(nxt)
            nxt += 1
        if not queue:
            t = wl["arrivals"][nxt]
            continue
        chunk = queue[:lanes]
        del queue[:lanes]
        wave = int(max(wl["frames"][u] for u in chunk))
        pool.reset_variable_tensors()
        for step in range(wave):
            pool.clear_inputs()
            for lane, uid in enumerate(chunk):
                k = min(step, wl["frames"][uid] - 1)
                pool.set_input(lane, 0, wl["inputs"][uid][k])
            pool.invoke()                       # real dispatch
            for uid in chunk:
                if wl["frames"][uid] == step + 1:
                    done_at[uid] = t + (step + 1) * dispatch_us
        t += wave * dispatch_us
    return done_at


def _measure_dispatch_us(model, resolver, lanes: int,
                         rng: np.random.Generator) -> Dict[str, float]:
    """Warm median cost of one dispatch for each discipline — the
    virtual clock's tick lengths."""
    xs = [rng.normal(0, 1, IN_SHAPE).astype(np.float32)
          for _ in range(lanes)]
    lock = InterpreterPool(model, resolver, batch=lanes)

    def lock_wave():
        lock.clear_inputs()
        for lane in range(lanes):
            lock.set_input(lane, 0, xs[lane])
        lock.invoke()
        lock.outputs(0)

    ragged = RaggedInterpreterPool()
    ragged.add_bucket("m", model, resolver, lanes=lanes)
    slots = [ragged.admit("m") for _ in range(max(1, lanes // 2))]

    def ragged_wave():
        for i, slot in enumerate(slots):
            ragged.set_input("m", slot, 0, xs[i])
        ragged.dispatch()
        ragged.outputs("m", 0)

    return {"lockstep": time_call(lock_wave, iters=20) * 1e6,
            "ragged": time_call(ragged_wave, iters=20) * 1e6}


def _latency_row(mode: str, lanes: int, occ: float, wl,
                 done_at: np.ndarray, dispatch_us: float) -> Dict:
    lat = done_at - wl["arrivals"]
    assert not np.isnan(lat).any(), f"{mode}: unfinished requests"
    slo = float((done_at <= wl["deadlines"]).mean())
    p50, p95, p99 = np.percentile(lat, (50, 95, 99))
    return {
        "mode": mode,
        "lanes": lanes,
        "occupancy_pct": int(round(100 * occ)),
        "n_requests": len(lat),
        "dispatch_us": round(dispatch_us, 1),
        "p50_us": round(float(p50), 1),
        "p95_us": round(float(p95), 1),
        "p99_us": round(float(p99), 1),
        "slo_attainment_pct": round(100 * slo, 1),
    }


# ---------------------------------------------------------------------------
# section 2: bucketed-prefill compile counts (the other half of PR 3)
# ---------------------------------------------------------------------------

def bench_prefill_buckets(lengths: Sequence[int] = (5, 7, 9, 12, 16, 17)
                          ) -> List[Dict]:
    """Mixed prompt lengths through a reduced dense ServingEngine:
    prefill compile count and total prefill seconds, exact-length vs
    bucketed (outputs are bit-identical — tests/test_scheduling.py)."""
    import jax

    from repro.configs import get_config
    from repro.models import get_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen3-32b", reduced=True)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab - 2, L).astype(np.int32)
               for L in lengths]
    rows = []
    for mode, buckets in (("exact", False), ("bucketed", None)):
        eng = ServingEngine(m, params, max_slots=2, cache_len=64,
                            prefill_buckets=buckets)
        for uid, toks in enumerate(prompts):
            eng.submit(Request(uid=uid, tokens=toks, max_new_tokens=2))
        eng.run()
        prefill_s = sum(r.prefill_s for r in eng.results.values())
        rows.append({
            "mode": f"prefill_{mode}",
            "prompt_lengths": len(prompts),
            "prefill_compiles": eng.prefill_compiles(),
            "buckets_hit": (len(eng.bucket_table.buckets())
                            if eng.bucket_table else 0),
            "total_prefill_s": round(prefill_s, 3),
        })
    return rows


# ---------------------------------------------------------------------------
# section 3 (--preempt): preemptible lanes under a heavy-tail mix
# ---------------------------------------------------------------------------

def _heavy_tail_workload(rng: np.random.Generator, n: int, lanes: int,
                         occupancy: float, dispatch_us: float) -> Dict:
    """The monopolizer mix: mostly 1-frame requests with a TIGHT
    deadline (arrival + TIGHT_SLO_TICKS dispatches), a MONO_FRAC tail
    of 6-frame best-effort streams (no deadline) that hold a lane for
    6 ticks unless preempted."""
    mono = rng.random(n) < MONO_FRAC
    frames = np.where(mono, FRAME_HI, 1)
    rate = occupancy * lanes / (float(frames.mean()) * dispatch_us)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    deadlines = np.where(
        mono, np.inf, arrivals + TIGHT_SLO_TICKS * dispatch_us)
    inputs = [[rng.normal(0, 1, IN_SHAPE).astype(np.float32)
               for _ in range(k)] for k in frames]
    return {"mono": mono, "frames": frames, "arrivals": arrivals,
            "deadlines": deadlines, "inputs": inputs}


def _sim_preempt(model, resolver, wl, lanes: int, dispatch_us: float,
                 preempt: Optional[str]) -> Dict[str, np.ndarray]:
    """Same tick loop as ``_sim_ragged`` with an optional preemption
    policy on the host; also returns per-request preemption counts."""
    clock = VirtualClock()
    host = MultiTenantHost(arena_bytes=64 << 20,
                           policy=get_policy("edf"), preempt=preempt,
                           clock=clock)
    host.add_ragged_micro("m", model, resolver, lanes=lanes,
                          bucket_lanes=False)
    n = len(wl["arrivals"])
    done_at = np.full(n, np.nan)
    nxt = 0
    while True:
        while nxt < n and wl["arrivals"][nxt] <= clock.now_us:
            d = wl["deadlines"][nxt]
            host.submit_micro(
                "m", nxt, [[x] for x in wl["inputs"][nxt]],
                deadline_us=None if np.isinf(d) else int(d),
                arrival_us=int(wl["arrivals"][nxt]))
            nxt += 1
        if not host._micro_pending():
            if nxt >= n:
                break
            clock.now_us = wl["arrivals"][nxt]
            continue
        host.micro_step()
        clock.now_us += dispatch_us
        for uid, res in host.micro_results["m"].items():
            if res.done and np.isnan(done_at[uid]):
                done_at[uid] = clock.now_us
    preemptions = np.array(
        [host.micro_results["m"][u].preemptions for u in range(n)])
    return {"done_at": done_at, "preemptions": preemptions}


def _preempt_row(mode: str, wl, sim: Dict, dispatch_us: float) -> Dict:
    lat = sim["done_at"] - wl["arrivals"]
    assert not np.isnan(lat).any(), f"{mode}: unfinished requests"
    dl = ~wl["mono"]                       # the deadline class
    p50, p99 = np.percentile(lat[dl], (50, 99))
    slo = float((sim["done_at"][dl] <= wl["deadlines"][dl]).mean())
    return {
        "mode": mode,
        "family": "fc-int8",       # the micro-lane rows' "model family"
        "lanes": PREEMPT_LANES,
        "n_deadline": int(dl.sum()),
        "n_monopolizers": int(wl["mono"].sum()),
        "dispatch_us": round(dispatch_us, 1),
        "deadline_p50_us": round(float(p50), 1),
        "deadline_p99_us": round(float(p99), 1),
        "deadline_slo_pct": round(100 * slo, 1),
        "mono_p99_us": round(float(np.percentile(lat[wl["mono"]], 99)),
                             1),
        "preemptions": int(sim["preemptions"].sum()),
    }


# ---------------------------------------------------------------------------
# section 4 (--preempt): pod engine, long-prompt monopolizer vs
# preemption + chunked prefill
# ---------------------------------------------------------------------------

def _engine_workload(rng: np.random.Generator, n: int, vocab: int,
                     decode_us: float, prefill_short_us: float,
                     arrival_scale: float = 1.0) -> Dict:
    """80% short deadline-class requests (5-token prompt, 4 new
    tokens), 20% long best-effort monopolizers (41-token prompt, 16 new
    tokens) whose one-shot prefill stalls every other slot.
    ``arrival_scale`` scales the mean inter-arrival gap (1.0 = the
    single-engine intensity; the replica sweep halves it so the
    offered load still exercises a multi-replica pod)."""
    mono = rng.random(n) < 0.2
    plens = np.where(mono, 41, 5)
    budgets = np.where(mono, 16, 4)
    service = prefill_short_us + 4 * decode_us   # deadline-class cost
    arrivals = np.cumsum(
        rng.exponential(arrival_scale * 3.0 * decode_us, n))
    deadlines = np.where(mono, np.inf, arrivals + 4.0 * service)
    prompts = [rng.integers(0, vocab - 2, L).astype(np.int32)
               for L in plens]
    return {"mono": mono, "prompts": prompts, "budgets": budgets,
            "arrivals": arrivals, "deadlines": deadlines}


def _autotuned_profile(bundle, params, tiny: bool):
    """The calibration profile the pod-engine sections run from: the
    on-disk cache when present (``benchmarks/results/profiles/``), else
    — on a full run only — a fresh calibration pass, persisted into the
    cache for every later run.  Tiny (CI smoke) never calibrates: a
    cache miss there just means hand defaults, keeping the smoke
    seconds-scale."""
    from repro.core import (calibrate, load_cached_profile,
                            profile_model_key, save_cached_profile)
    prof = load_cached_profile(profile_model_key(bundle.cfg, 64))
    if prof is not None or tiny:
        return prof
    # the engine workload's prompt mix: 80% short (5), 20% long (41)
    prof = calibrate(bundle, params, [5] * 8 + [41] * 2,
                     cache_len=64, seed=SEED, iters=3,
                     decode_slots=(2,), block_candidates=(8, 16, 32))
    save_cached_profile(prof)
    return prof


def _measure_engine_costs(bundle, params, chunk: int) -> Dict:
    """Warm per-dispatch costs of the engine's three step kinds —
    decode, one-shot prefill per padded length, one chunk — the
    virtual clock's tick vocabulary.  Family-generic: the cache leaf
    synced on is whatever the bundle's pytree holds (KV rings or
    recurrent state), the chunk dispatch follows the family's chunk-op
    signature, and ``chunk=0`` (moe: no chunked prefill) skips the
    chunk measurement entirely."""
    import jax
    import jax.numpy as jnp

    from repro.serving import ServingEngine

    def _sync(x):
        return jax.tree.leaves(x)[0].block_until_ready()

    eng = ServingEngine(bundle, params, max_slots=2, cache_len=64,
                        prefill_chunk=chunk or None)
    rng = np.random.default_rng(SEED)
    costs: Dict = {}
    for L in sorted({chunk or 8, 8, 64}):
        toks = jnp.asarray(rng.integers(
            0, bundle.cfg.vocab - 2, L).astype(np.int32)[None])
        costs[("prefill", L)] = time_call(
            lambda t=toks: _sync(
                eng._prefill((params, {"tokens": t}))[1]),
            warmup=1, iters=5) * 1e6
    if chunk:
        cache1 = bundle.empty_cache(1, 64, bundle.cfg.jnp_dtype())
        toks = jnp.asarray(rng.integers(
            0, bundle.cfg.vocab - 2, chunk).astype(np.int32)[None])
        if eng._recurrent_chunk:
            args = (params, cache1, toks, jnp.int32(8),
                    jnp.int32(chunk))
        else:
            args = (params, cache1, toks, jnp.int32(8))
        costs["chunk"] = time_call(
            lambda: _sync(eng._prefill_chunk(args)),
            warmup=1, iters=5) * 1e6
    # the serving loop's dispatch: the engine's KV donated and kept
    eng.cur_tokens = jnp.zeros((2, 1), jnp.int32)
    eng.lengths = jnp.asarray([8, 8], jnp.int32)
    costs["decode"] = time_call(lambda: _sync(eng._dispatch_decode()),
                                warmup=1, iters=5) * 1e6
    return costs


def _sim_engine(bundle, params, wl, mode: str, costs: Dict,
                chunk: int, profile=None) -> np.ndarray:
    """Drive a REAL ServingEngine tick by tick on the virtual clock,
    advancing it by the measured cost of what each step actually did
    (``ServingEngine.last_step``).  Returns completion times (µs).
    The chunked mode constructs its engine through ``from_profile``
    when a calibration profile is available, so the benchmark runs the
    autotuned configuration (bucket table, solved kv_block) rather
    than hand constants."""
    from repro.serving import Request, ServingEngine

    kw: Dict = {}
    if "preempt" in mode:
        kw["preempt"] = "edf-displace"
    if "chunk" in mode:
        kw["prefill_chunk"] = chunk
    if "bucket" in mode:
        # the moe fast-path mode: capacity-stable bucketed prefill in
        # place of chunking (moe cannot chunk); its siblings run
        # exact-length so the contrast isolates bucketing
        kw["prefill_buckets"] = True
    elif bundle.cfg.family == "moe":
        kw["prefill_buckets"] = False
    clock = VirtualClock()
    if "chunk" in mode and profile is not None:
        # prefill_buckets pinned to the engine default so this mode
        # differs from its siblings only in chunking (+ the profile's
        # solved kv_block) — the bucket-table comparison has its own
        # benchmark (autotune)
        eng = ServingEngine.from_profile(
            bundle, params, profile, max_slots=2, cache_len=64,
            policy="edf", clock=clock, prefill_buckets=None, **kw)
    else:
        eng = ServingEngine(bundle, params, max_slots=2, cache_len=64,
                            policy="edf", clock=clock, **kw)
    n = len(wl["arrivals"])
    done_at = np.full(n, np.nan)
    nxt = 0
    while True:
        while nxt < n and wl["arrivals"][nxt] <= clock.now_us:
            d = wl["deadlines"][nxt]
            eng.submit(Request(
                uid=nxt, tokens=wl["prompts"][nxt],
                max_new_tokens=int(wl["budgets"][nxt]),
                deadline_us=None if np.isinf(d) else int(d),
                arrival_us=int(wl["arrivals"][nxt])))
            nxt += 1
        more = eng.step()
        ev = eng.last_step
        dt = ev["chunks"] * costs.get("chunk", 0.0)
        if ev["decoded"]:
            dt += costs["decode"]
        for L in ev["prefill_tokens"]:
            cost = costs.get(("prefill", L))
            if cost is None:               # interpolate on tokens
                cost = costs[("prefill", 64)] * (L / 64.0)
            dt += cost
        clock.now_us += max(dt, 1.0)
        for uid, res in eng.results.items():
            if res.done and np.isnan(done_at[uid]):
                done_at[uid] = clock.now_us
        if not more:
            if nxt >= n:
                break
            clock.now_us = max(clock.now_us, wl["arrivals"][nxt])
    return done_at


def _engine_row(mode: str, family: str, wl,
                done_at: np.ndarray) -> Dict:
    lat = done_at - wl["arrivals"]
    assert not np.isnan(lat).any(), f"{family}/{mode}: unfinished " \
        "requests"
    dl = ~wl["mono"]
    p50, p99 = np.percentile(lat[dl], (50, 99))
    slo = float((done_at[dl] <= wl["deadlines"][dl]).mean())
    return {
        "mode": mode,
        "family": family,
        "slots": 2,
        "n_deadline": int(dl.sum()),
        "n_monopolizers": int(wl["mono"].sum()),
        "deadline_p50_us": round(float(p50), 1),
        "deadline_p99_us": round(float(p99), 1),
        "deadline_slo_pct": round(100 * slo, 1),
        "mono_p99_us": round(float(np.percentile(lat[wl["mono"]], 99)),
                             1),
    }


def run_preempt(tiny: bool = False) -> List[Dict]:
    """The --preempt benchmark: heavy-tail micro mix (EDF vs
    EDF+preemption over checkpointable lanes) plus the pod-engine
    monopolizer (EDF vs +preemption vs +preemption+chunked prefill).
    Emits ``BENCH_preemption.json`` unless ``tiny``."""
    lanes = PREEMPT_LANES
    n = 32 if tiny else PREEMPT_N
    resolver = AllOpsResolver()
    model = _build_model()
    rng = np.random.default_rng(SEED)
    cost = _measure_dispatch_us(model, resolver, lanes, rng)

    wl = _heavy_tail_workload(np.random.default_rng(SEED + 2), n, lanes,
                              PREEMPT_OCC, cost["ragged"])
    rows: List[Dict] = []
    for mode, preempt in (("edf", None),
                          ("edf_preempt", "edf-displace")):
        sim = _sim_preempt(model, resolver, wl, lanes, cost["ragged"],
                           preempt)
        rows.append(_preempt_row(mode, wl, sim, cost["ragged"]))
    print_table("Preemptible lanes (heavy-tail mix: 1-frame deadline "
                "class + 6-frame best-effort monopolizers)", rows)

    # pod engine: long-prompt monopolizer, swept over the FULL family
    # matrix — every family whose fast paths the engine now supports
    # runs the same workload shape (family parity, PR 7).  The third
    # mode is the family's long-prompt fast path: chunked prefill for
    # chunkable families, capacity-stable bucketed prefill for moe
    # (which cannot chunk).
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    erows: List[Dict] = []
    families = [("dense", "qwen3-32b"), ("ssm", "mamba2-780m"),
                ("hybrid", "zamba2-1.2b"), ("moe", "deepseek-moe-16b")]
    for family, arch in families:
        cfg = get_config(arch, reduced=True)
        bundle = get_model(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        # the calibration-profile path (cache or fresh calibration) is
        # the dense flagship's; other families run the hand default so
        # one full sweep stays minutes-scale
        prof = (_autotuned_profile(bundle, params, tiny)
                if family == "dense" else None)
        # the hand default (8) survives only as the cache-miss
        # fallback — and when the solver decided chunking off (this
        # section exists to show the long-prompt fast path, so it
        # stays on here); moe: chunk=0, its fast path is bucketing
        if family == "moe":
            chunk = 0
        else:
            chunk = (int(prof.prefill_chunk)
                     if prof is not None and prof.prefill_chunk else 8)
        costs = _measure_engine_costs(bundle, params, chunk)
        ewl = _engine_workload(
            np.random.default_rng(SEED + 3),
            (12 if family == "dense" else 8) if tiny
            else (40 if family == "dense" else 24),
            cfg.vocab, costs["decode"], costs[("prefill", 8)])
        fast = ("engine_edf_preempt_bucket" if family == "moe"
                else "engine_edf_preempt_chunk")
        for mode in ("engine_edf", "engine_edf_preempt", fast):
            done = _sim_engine(bundle, params, ewl, mode, costs, chunk,
                               profile=prof)
            erows.append(_engine_row(mode, family, ewl, done))
    print_table("Pod engine (short deadline class + long-prompt "
                "best-effort monopolizers), full family matrix", erows)

    all_rows = rows + erows
    if not tiny:
        save_result("BENCH_preemption", all_rows, seed=SEED)
    return all_rows


# ---------------------------------------------------------------------------
# section 5 (--paged): paged KV pool vs contiguous slabs at the same
# HBM budget — occupancy AND bit-identity
# ---------------------------------------------------------------------------

PAGED_CONTIG_SLOTS = 2       # the HBM budget: 2 whole cache_len slabs
PAGED_CACHE_LEN = 64
PAGED_SLOT_CAP = 8           # keep the paged decode batch modest


def run_paged(tiny: bool = False) -> List[Dict]:
    """The paged-KV occupancy benchmark: a flood of short requests
    (each needing a fraction of cache_len) served by a contiguous
    engine — admission bounded by whole-slab slots — and by a paged
    engine whose pool holds the SAME number of KV rows carved into
    blocks.  Reports peak concurrent occupancy, the HBM spent, and
    whether the decoded tokens stayed bit-identical (they must: the
    paged path is a layout change, never a semantics change).  The
    block size comes from the calibration-profile cache when one was
    solved (``profile.kv_block``), else the hand default 16.  Emits
    ``BENCH_paged_kv.json`` unless ``tiny``."""
    import jax

    from repro.configs import get_config
    from repro.core import load_cached_profile, profile_model_key
    from repro.models import get_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("qwen3-32b", reduced=True)
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    cache_len = PAGED_CACHE_LEN
    prof = load_cached_profile(profile_model_key(cfg, cache_len))
    bs = (int(prof.kv_block) if prof is not None and prof.kv_block
          and cache_len % prof.kv_block == 0 else 16)
    budget_rows = PAGED_CONTIG_SLOTS * cache_len
    pool_blocks = budget_rows // bs          # same rows, one is garbage
    plen, budget = 5, 4                      # 8 KV rows per request
    blocks_per_req = -(-(plen - 1 + budget) // bs)
    paged_slots = min((pool_blocks - 1) // blocks_per_req,
                      PAGED_SLOT_CAP)
    n = 8 if tiny else 24
    rng = np.random.default_rng(SEED + 4)
    prompts = [rng.integers(0, cfg.vocab - 2, plen).astype(np.int32)
               for _ in range(n)]

    def _serve(paged: bool):
        if paged:
            eng = ServingEngine(bundle, params, max_slots=paged_slots,
                                cache_len=cache_len, policy="fifo",
                                kv_block=bs, kv_pool_blocks=pool_blocks)
        else:
            eng = ServingEngine(bundle, params,
                                max_slots=PAGED_CONTIG_SLOTS,
                                cache_len=cache_len, policy="fifo")
        for uid, toks in enumerate(prompts):
            eng.submit(Request(uid=uid, tokens=toks,
                               max_new_tokens=budget))
        peak = steps = 0
        while True:
            more = eng.step()
            steps += 1
            peak = max(peak, int(eng.active.sum()))
            if not more:
                break
        outs = [list(eng.results[u].output) for u in range(n)]
        return eng, peak, steps, outs

    ceng, cpeak, csteps, couts = _serve(paged=False)
    peng, ppeak, psteps, pouts = _serve(paged=True)
    match = pouts == couts
    assert match, "paged decode diverged from contiguous — layout " \
                  "changes must never change tokens"
    gain = round(ppeak / max(cpeak, 1), 2)
    rows = [
        {"mode": "contiguous", "hbm_bytes": int(ceng.kv_bytes),
         "kv_block": 0, "max_slots": PAGED_CONTIG_SLOTS,
         "n_requests": n, "peak_concurrent": cpeak, "steps": csteps,
         "occupancy_gain": 1.0, "tokens_match": True},
        {"mode": "paged", "hbm_bytes": int(peng.kv_bytes),
         "kv_block": bs, "max_slots": paged_slots,
         "n_requests": n, "peak_concurrent": ppeak, "steps": psteps,
         "occupancy_gain": gain, "tokens_match": bool(match)},
    ]
    print_table("Paged KV pool vs contiguous slabs "
                f"(same HBM budget: {budget_rows} KV rows)", rows)
    if not tiny:
        save_result("BENCH_paged_kv", rows, seed=SEED)
    return rows


# ---------------------------------------------------------------------------
# section 6 (--replicas): data-parallel replica routing sweep
# ---------------------------------------------------------------------------

REPLICA_COUNTS = (1, 2, 4)
REPLICA_POLICIES = ("round-robin", "least-loaded", "locality")


def _sim_replicas(bundle, params, wl, n_replicas: int, routing: str,
                  costs: Dict) -> Dict:
    """Serve the engine arrival mix through ``n_replicas`` REAL engine
    replicas behind a ``ReplicaRouter``: per tick the router routes
    arrivals, every replica advances one engine step, and the virtual
    clock moves by the MAX of the replicas' measured step costs
    (replicas run in parallel — the tick is as long as the slowest
    replica's dispatch).  The policies form a ladder: round-robin and
    least-loaded are admission-time-only placement, while locality
    additionally runs the router's stickiness-aware rebalancer each
    tick (``rebalance=True``) — work stealing that never touches
    checkpointed requests.  Returns completion times, total decoded
    tokens, makespan, and per-uid outputs for the bit-identity
    check."""
    from repro.serving import ReplicaRouter, Request, ServingEngine

    clock = VirtualClock()
    engs = [ServingEngine(bundle, params, max_slots=2, cache_len=64,
                          policy="edf", clock=clock)
            for _ in range(n_replicas)]
    router = ReplicaRouter(engs, routing=routing,
                           rebalance=(routing == "locality"))
    n = len(wl["arrivals"])
    done_at = np.full(n, np.nan)
    nxt = 0
    while True:
        while nxt < n and wl["arrivals"][nxt] <= clock.now_us:
            d = wl["deadlines"][nxt]
            router.submit(Request(
                uid=nxt, tokens=wl["prompts"][nxt],
                max_new_tokens=int(wl["budgets"][nxt]),
                deadline_us=None if np.isinf(d) else int(d),
                arrival_us=int(wl["arrivals"][nxt])))
            nxt += 1
        if router.rebalance and len(engs) > 1:
            router._rebalance()
        more = False
        dt = 0.0
        for eng in engs:
            if eng.step():
                more = True
            ev = eng.last_step
            d_r = 0.0
            if ev["decoded"]:
                d_r += costs["decode"]
            for L in ev["prefill_tokens"]:
                cost = costs.get(("prefill", L))
                if cost is None:
                    cost = costs[("prefill", 64)] * (L / 64.0)
                d_r += cost
            dt = max(dt, d_r)
        clock.now_us += max(dt, 1.0)
        for uid, res in router.results.items():
            if res.done and np.isnan(done_at[uid]):
                done_at[uid] = clock.now_us
        if not more:
            if nxt >= n:
                break
            clock.now_us = max(clock.now_us, wl["arrivals"][nxt])
    outs = {u: list(r.output) for u, r in router.results.items()}
    tokens = sum(len(o) for o in outs.values())
    return {"done_at": done_at, "outputs": outs, "tokens": tokens,
            "makespan_us": clock.now_us}


def _replica_row(replicas: int, policy: str, wl, sim: Dict,
                 base_outputs: Dict) -> Dict:
    """One sweep row.  The latency percentiles are over the DEADLINE
    class only (like BENCH_preemption's deadline_p50/p99): the
    monopolizers are best-effort and their completion latency is
    dominated by their own 16-token service time, which no routing
    policy can change — folding them in would bury the queueing delay
    routing actually controls."""
    lat = sim["done_at"] - wl["arrivals"]
    assert not np.isnan(lat).any(), \
        f"replicas={replicas}/{policy}: unfinished requests"
    dl = ~wl["mono"]
    slo = float((sim["done_at"][dl] <= wl["deadlines"][dl]).mean())
    return {
        "replicas": replicas,
        "policy": policy,
        "n_requests": len(lat),
        "throughput": round(
            sim["tokens"] / (sim["makespan_us"] * 1e-6), 1),
        "p50_us": round(float(np.percentile(lat[dl], 50)), 1),
        "p99_us": round(float(np.percentile(lat[dl], 99)), 1),
        "slo": round(100 * slo, 1),
        "tokens_match": sim["outputs"] == base_outputs,
    }


def run_replicas(tiny: bool = False) -> List[Dict]:
    """The --replicas benchmark: replica count × routing policy over
    the PR-4 engine arrival mix.  Emits ``BENCH_replica_sweep.json``
    unless ``tiny``."""
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config("qwen3-32b", reduced=True)
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    costs = _measure_engine_costs(bundle, params, chunk=0)
    n = 16 if tiny else 60
    # half the single-engine inter-arrival gap: the sweep provisions
    # up to 4 replicas, and an arrival process a single engine can
    # absorb leaves a 4-replica pod idle enough that every routing
    # policy looks the same — the composition (80/20 mix, budgets,
    # deadlines) is untouched
    wl = _engine_workload(np.random.default_rng(SEED + 5), n,
                          cfg.vocab, costs["decode"],
                          costs[("prefill", 8)], arrival_scale=0.5)
    counts = (1, 2) if tiny else REPLICA_COUNTS
    # the single-replica round-robin run IS the exact baseline every
    # other config's tokens are checked against
    base = _sim_replicas(bundle, params, wl, 1, "round-robin", costs)
    rows = [_replica_row(1, "round-robin", wl, base, base["outputs"])]
    for r in counts:
        for policy in REPLICA_POLICIES:
            if r == 1 and policy == "round-robin":
                continue            # already the baseline row
            sim = _sim_replicas(bundle, params, wl, r, policy, costs)
            rows.append(_replica_row(r, policy, wl, sim,
                                     base["outputs"]))
    assert all(row["tokens_match"] for row in rows), \
        "routing changed decoded tokens — placement must never " \
        "change semantics"
    print_table("Replica routing sweep (PR-4 arrival mix, "
                "replicas × policy)", rows)
    if not tiny:
        save_result("BENCH_replica_sweep", rows, seed=SEED)
    return rows


# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# section 7 (--stream): TTFT/ITL percentiles, sync vs overlapped decode
# ---------------------------------------------------------------------------

STREAM_FAMILIES_SWEEP = (("dense", "qwen3-32b"), ("ssm", "mamba2-780m"),
                         ("hybrid", "zamba2-1.2b"),
                         ("moe", "deepseek-moe-16b"))
STREAM_ARRIVAL_SCALE = 0.5     # pod intensity: keeps both slots busy
WALL_STREAM_BUDGET = 24        # wall section: tokens per request


def _measure_host_us(bundle, params, eng) -> float:
    """Warm cost of the sync loop's per-tick HOST leg: the blocking
    logits readback plus greedy sampling — exactly what the overlapped
    loop hides under the next device step.  Measured on a logits
    buffer that is already device-ready so the device compute itself
    (``costs['decode']``) is not double-counted."""
    import jax
    import jax.numpy as jnp

    cur = jnp.zeros((2, 1), jnp.int32)
    lens = jnp.asarray([8, 8], jnp.int32)
    cache2 = bundle.empty_cache(2, 64, bundle.cfg.jnp_dtype())
    logits, _ = eng._decode.consume((params, cache2, cur, lens))
    jax.block_until_ready(logits)
    return time_call(lambda: eng._sample(logits, 0.0),
                     warmup=2, iters=20) * 1e6


def _sim_stream(bundle, params, wl, overlap: bool,
                costs: Dict) -> Dict:
    """Serve ``wl`` on a REAL engine over the virtual clock with
    per-token StreamEvents collected, sync or overlapped.  The tick
    costs encode the overlap: a sync tick pays ``decode + host`` in
    sequence; an overlapped tick pays ``max(decode, host)`` because
    the host leg (previous step's readback + sampling + emission) runs
    while the device executes the dispatched step.  Returns the event
    stream, outputs, and the mean decode-tick occupancy."""
    from repro.serving import Request, ServingEngine

    events: List = []
    clock = VirtualClock()
    eng = ServingEngine(bundle, params, max_slots=2, cache_len=64,
                        policy="edf", clock=clock,
                        prefill_buckets=False, overlap=overlap,
                        on_token=events.append)
    n = len(wl["arrivals"])
    nxt = 0
    occ: List[float] = []
    while True:
        while nxt < n and wl["arrivals"][nxt] <= clock.now_us:
            d = wl["deadlines"][nxt]
            eng.submit(Request(
                uid=nxt, tokens=wl["prompts"][nxt],
                max_new_tokens=int(wl["budgets"][nxt]),
                deadline_us=None if np.isinf(d) else int(d),
                arrival_us=int(wl["arrivals"][nxt])))
            nxt += 1
        more = eng.step()
        ev = eng.last_step
        dec = costs["decode"] if ev["decoded"] else 0.0
        host = costs["host"] if ev["processed"] else 0.0
        dt = max(dec, host) if overlap else dec + host
        for L in ev["prefill_tokens"]:
            cost = costs.get(("prefill", L))
            if cost is None:
                cost = costs[("prefill", 64)] * (L / 64.0)
            dt += cost
        clock.now_us += max(dt, 1.0)
        if ev["decoded"]:
            occ.append(float(eng.active.sum() + len(eng._chunking))
                       / eng.max_slots)
        if not more:
            if nxt >= n:
                break
            clock.now_us = max(clock.now_us, wl["arrivals"][nxt])
    outs = [list(eng.results[u].output) for u in range(n)]
    return {"events": events, "outs": outs,
            "occupancy": float(np.mean(occ)) if occ else 0.0}


def _stream_metrics(events, arrivals) -> Dict:
    """TTFT (first event stamp − arrival) and ITL (gaps between a
    request's consecutive event stamps) percentiles from one event
    stream."""
    per: Dict[int, List] = {}
    for e in events:
        per.setdefault(e.uid, []).append(e)
    ttft = [seq[0].t_us - arrivals[uid] for uid, seq in per.items()]
    itl = [b.t_us - a.t_us for seq in per.values()
           for a, b in zip(seq, seq[1:])]
    t50, t95 = np.percentile(ttft, (50, 95))
    i50, i95 = np.percentile(itl, (50, 95)) if itl else (0.0, 0.0)
    return {"ttft_p50_us": round(float(t50), 1),
            "ttft_p95_us": round(float(t95), 1),
            "itl_p50_us": round(float(i50), 1),
            "itl_p95_us": round(float(i95), 1)}


def _wall_stream(bundle, params, overlap: bool, n: int) -> Dict:
    """The wall-clock leg: the same saturated decode workload served
    on REAL time (the engine's default µs clock), events stamped as
    the host learns each token.  A warmup request is served first so
    compile time never pollutes the percentiles; occupancy is sampled
    per step like the virtual leg."""
    from repro.serving import Request, ServingEngine

    events: List = []
    eng = ServingEngine(bundle, params, max_slots=2, cache_len=64,
                        prefill_buckets=False, overlap=overlap,
                        on_token=events.append)
    rng = np.random.default_rng(SEED + 5)
    prompts = [rng.integers(0, bundle.cfg.vocab - 2, 5).astype(np.int32)
               for _ in range(n)]
    eng.submit(Request(uid=10_000, tokens=prompts[0].copy(),
                       max_new_tokens=4))
    eng.run()
    events.clear()                      # warmup over: compiles are paid
    arr = {}
    for uid, toks in enumerate(prompts):
        req = Request(uid=uid, tokens=toks,
                      max_new_tokens=WALL_STREAM_BUDGET)
        eng.submit(req)                 # arrival stamped at submit
        arr[uid] = req.arrival_us
    occ: List[float] = []
    while eng.step():
        if eng.last_step["decoded"]:
            occ.append(float(eng.active.sum()) / eng.max_slots)
    eng.drain()
    outs = [list(eng.results[u].output) for u in range(n)]
    return {"events": [e for e in events if e.uid < 10_000],
            "outs": outs, "arrivals": arr,
            "occupancy": float(np.mean(occ)) if occ else 0.0}


def run_stream(tiny: bool = False) -> List[Dict]:
    """The --stream benchmark: TTFT/ITL percentiles from per-token
    StreamEvents, sync vs overlapped decode over the family matrix on
    the virtual clock, plus the dense wall-clock validation leg.
    Tokens must be bit-identical between modes in every comparison.
    Emits ``BENCH_streaming.json`` unless ``tiny``."""
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    rows: List[Dict] = []
    for family, arch in STREAM_FAMILIES_SWEEP:
        cfg = get_config(arch, reduced=True)
        bundle = get_model(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        costs = _measure_engine_costs(bundle, params, 0)
        from repro.serving import ServingEngine
        probe = ServingEngine(bundle, params, max_slots=2,
                              cache_len=64, prefill_buckets=False)
        costs["host"] = _measure_host_us(bundle, params, probe)
        wl = _engine_workload(
            np.random.default_rng(SEED + 6), 8 if tiny else 32,
            cfg.vocab, costs["decode"], costs[("prefill", 8)],
            arrival_scale=STREAM_ARRIVAL_SCALE)
        sims = {m: _sim_stream(bundle, params, wl, m == "overlap",
                               costs)
                for m in ("sync", "overlap")}
        match = sims["sync"]["outs"] == sims["overlap"]["outs"]
        assert match, f"{family}: overlapped decode changed tokens"
        for mode, sim in sims.items():
            rows.append({
                "family": family, "mode": mode, "clock": "virtual",
                "n_requests": len(wl["arrivals"]),
                "occupancy_pct": round(100 * sim["occupancy"], 1),
                "decode_us": round(costs["decode"], 1),
                "host_us": round(costs["host"], 1),
                **_stream_metrics(sim["events"], wl["arrivals"]),
                "tokens_match": bool(match)})
    print_table("Streaming TTFT/ITL, sync vs overlapped decode "
                "(virtual clock, family matrix)", rows)

    # wall-clock validation: dense flagship, saturated slots, real time
    cfg = get_config("qwen3-32b", reduced=True)
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    costs = _measure_engine_costs(bundle, params, 0)
    from repro.serving import ServingEngine
    probe = ServingEngine(bundle, params, max_slots=2, cache_len=64,
                          prefill_buckets=False)
    costs["host"] = _measure_host_us(bundle, params, probe)
    predicted = (costs["decode"] + costs["host"]) \
        / max(costs["decode"], costs["host"])
    n = 4 if tiny else 8
    walls = {m: _wall_stream(bundle, params, m == "overlap", n)
             for m in ("sync", "overlap")}
    match = walls["sync"]["outs"] == walls["overlap"]["outs"]
    assert match, "wall-clock overlapped decode changed tokens"
    wmet = {m: _stream_metrics(w["events"], w["arrivals"])
            for m, w in walls.items()}
    observed = wmet["sync"]["itl_p50_us"] \
        / max(wmet["overlap"]["itl_p50_us"], 1e-9)
    wrows = []
    for mode, w in walls.items():
        wrows.append({
            "family": "dense", "mode": mode, "clock": "wall",
            "n_requests": n,
            "occupancy_pct": round(100 * w["occupancy"], 1),
            "decode_us": round(costs["decode"], 1),
            "host_us": round(costs["host"], 1),
            **wmet[mode],
            "predicted_itl_ratio": round(float(predicted), 3),
            "observed_itl_ratio": round(float(observed), 3),
            "tokens_match": bool(match)})
    print_table("Wall-clock validation (dense, saturated slots): "
                f"cost model predicts sync/overlap ITL "
                f"{predicted:.3f}x", wrows)
    all_rows = rows + wrows
    if not tiny:
        save_result("BENCH_streaming", all_rows, seed=SEED)
    return all_rows


def run(tiny: bool = False) -> List[Dict]:
    lanes = 4 if tiny else LANES
    n = 24 if tiny else N_REQUESTS
    occupancies = (0.5,) if tiny else OCCUPANCIES
    resolver = AllOpsResolver()
    model = _build_model()
    rng = np.random.default_rng(SEED)
    cost = _measure_dispatch_us(model, resolver, lanes, rng)

    rows: List[Dict] = []
    for occ in occupancies:
        wl = _workload(np.random.default_rng(SEED + 1), n, lanes, occ,
                       cost["ragged"])
        done = _sim_lockstep(model, resolver, wl, lanes,
                             cost["lockstep"])
        rows.append(_latency_row("lockstep_fifo", lanes, occ, wl, done,
                                 cost["lockstep"]))
        for policy in ("fifo", "edf"):
            done = _sim_ragged(model, resolver, wl, lanes,
                               cost["ragged"], policy)
            rows.append(_latency_row(f"ragged_{policy}", lanes, occ, wl,
                                     done, cost["ragged"]))
    print_table("Arrival-process completion latency "
                "(Poisson arrivals, ragged 1..6-frame requests)", rows)

    prefill_rows = bench_prefill_buckets(
        lengths=(5, 7, 9) if tiny else (5, 7, 9, 12, 16, 17))
    print_table("Bucketed prefill (mixed prompt lengths, one engine)",
                prefill_rows)
    all_rows = rows + prefill_rows
    if not tiny:
        save_result("BENCH_arrival_process", all_rows, seed=SEED)
    return all_rows


if __name__ == "__main__":
    if "--preempt" in sys.argv[1:]:
        run_preempt(tiny="--tiny" in sys.argv[1:])
    elif "--paged" in sys.argv[1:]:
        run_paged(tiny="--tiny" in sys.argv[1:])
    elif "--replicas" in sys.argv[1:]:
        run_replicas(tiny="--tiny" in sys.argv[1:])
    elif "--stream" in sys.argv[1:]:
        run_stream(tiny="--tiny" in sys.argv[1:])
    else:
        run(tiny="--tiny" in sys.argv[1:])
