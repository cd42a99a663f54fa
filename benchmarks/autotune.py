"""Autotune benchmark: calibration-driven serving config vs the
hand-picked defaults.

``repro.core.costmodel.calibrate`` measures, on the REAL compiled
serving steps, each candidate bucket's (compile, padded-step) cost and
each candidate chunk size's step cost, then solves for the bucket
table and ``prefill_chunk`` that minimize the workload's expected
prefill latency.  This benchmark shows what that buys on the PR-3/PR-4
arrival process (``benchmarks.arrival_process`` supplies the workload
generator and the virtual clock):

  * **config section** — the solved layout next to the default pow2
    ladder: bucket levels, chunk size, prefill compiles actually
    traced (``ServingEngine.prefill_compiles`` vs the profile's
    ``predicted_compiles``), total padded prefill tokens, and a
    ``tokens_match_default`` bit asserting the autotuned engine's
    decoded tokens are BIT-IDENTICAL to the default engine's (padding
    is invisible to the length-masked decode, so tuning the table can
    never change the output);
  * **latency section** — p50/p95 completion latency and deadline-SLO
    attainment for the same Poisson arrival process served by each
    config, on a virtual clock that charges each engine step what
    calibration MEASURED it to cost — including the one-time compile
    stall the first hit of every bucket pays, which is exactly the
    cost the solver trades against padding waste.

Emits ``BENCH_autotune.json`` via ``python -m benchmarks.run
autotune``; ``--tiny`` runs a seconds-scale smoke (no JSON written)
used by the CI pipeline.  How to read the rows: docs/SCHEDULING.md
("Cost model & calibration").
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Set

import numpy as np

from .arrival_process import SEED, VirtualClock, _engine_workload
from .common import print_table, save_result

CACHE_LEN = 64
SLOTS = 2
N_REQUESTS = 40
N_CALIB = 200          # length samples the profile is solved against
# candidate levels: the default pow2 ladder PLUS the workload's own
# lengths, so both configs' padded lengths have measured costs
CANDIDATES = (8, 16, 32, 40, 64)
CHUNKS = (0, 8)


# the full family matrix (family parity, PR 7): every family with a
# bucketed or chunked fast path to size is calibrated and served.
# dense/moe solve bucket tables (moe via capacity-stable masked
# dispatch); ssm/hybrid solve only the chunk size (their prefill stays
# exact-length, so their chunk candidates are the whole search space).
FAMILIES = (("dense", "qwen3-32b"), ("ssm", "mamba2-780m"),
            ("hybrid", "zamba2-1.2b"), ("moe", "deepseek-moe-16b"))


def _build(arch: str = "qwen3-32b"):
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config(arch, reduced=True)
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


def _measure_decode_us(bundle, params) -> float:
    """Warm cost of one fused decode step — the virtual clock's decode
    tick (its compile is warmed out: both configs pay it identically
    at engine start, before any request arrives)."""
    import jax.numpy as jnp

    from repro.core.profiler import measure_compile_and_step
    from repro.serving import ServingEngine

    eng = ServingEngine(bundle, params, max_slots=SLOTS,
                        cache_len=CACHE_LEN, prefill_buckets=False)
    # the serving loop's dispatch: the engine's KV donated and kept
    eng.cur_tokens = jnp.zeros((SLOTS, 1), jnp.int32)
    eng.lengths = jnp.asarray([8] * SLOTS, jnp.int32)
    t = measure_compile_and_step(eng._dispatch_decode, iters=5)
    return t.step_us


class _CostClock:
    """Charges each engine step what calibration measured: warm step
    cost per padded prefill length / chunk / decode, plus the COLD
    compile cost the first time a prefill length is traced — the
    virtual-clock analogue of ``jit``'s per-signature cache."""

    def __init__(self, profile, decode_us: float, chunk: int):
        self.by_len = {c.length: c for c in profile.bucket_costs}
        self.chunk_cost = next(
            (c for c in profile.chunk_costs if c.chunk == chunk), None)
        self.decode_us = decode_us
        self.seen: Set[int] = set()
        self.chunk_seen = False
        self.compile_stall_us = 0.0

    def _prefill(self, L: int) -> float:
        c = self.by_len.get(L)
        if c is None:                       # off-candidate length:
            ref = min(self.by_len.values(),  # nearest measured level
                      key=lambda r: abs(r.length - L))
            cold, warm = ref.compile_us, ref.step_us * L / ref.length
        else:
            cold, warm = c.compile_us, c.step_us
        if L not in self.seen:
            self.seen.add(L)
            self.compile_stall_us += cold - warm
            return cold
        return warm

    def step_cost(self, last_step: Dict) -> float:
        dt = 0.0
        for L in last_step["prefill_tokens"]:
            dt += self._prefill(L)
        if last_step["chunks"]:
            cc = self.chunk_cost
            dt += last_step["chunks"] * cc.step_us
            if not self.chunk_seen:
                self.chunk_seen = True
                self.compile_stall_us += cc.trace_overhead_us
                dt += cc.trace_overhead_us
        if last_step["decoded"]:
            dt += self.decode_us
        return dt


def _padded_len(eng, prompt_len: int) -> int:
    """How many prefill tokens a prompt of this length actually costs
    under the engine's config: its chunked total, its bucket, or its
    exact length — the padding-waste metric the config rows report.
    Eligibility is asked of the ENGINE's own predicates
    (``_chunk_eligible``, ``_vis``) so this metric cannot drift from
    what the engine actually dispatches."""
    from repro.serving import Request

    m = prompt_len - 1
    if m < 1:
        return 0
    probe = Request(uid=-1, tokens=np.zeros(prompt_len, np.int32))
    if eng._chunk_eligible(probe):
        return -(-m // eng.chunk_tokens) * eng.chunk_tokens
    if eng.bucket_table is not None:
        b = eng.bucket_table.fit(m)
        if b is not None and b <= eng.cache_len - eng._vis():
            return b
    return m


def _sim(bundle, params, wl, profile, decode_us: float,
         tuned: bool) -> Dict:
    """Serve the arrival process with REAL dispatches; account latency
    on the measured-cost virtual clock.  Returns completion times,
    decoded tokens, and the engine's observability counters."""
    from repro.serving import Request, ServingEngine

    clock = VirtualClock()
    if tuned:
        eng = ServingEngine.from_profile(
            bundle, params, profile, max_slots=SLOTS, policy="edf",
            clock=clock)
    else:
        eng = ServingEngine(bundle, params, max_slots=SLOTS,
                            cache_len=CACHE_LEN, policy="edf",
                            clock=clock)
    cost = _CostClock(profile, decode_us, eng.chunk_tokens)
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
        clock.now_us += max(cost.step_cost(eng.last_step), 1.0)
        for uid, res in eng.results.items():
            if res.done and np.isnan(done_at[uid]):
                done_at[uid] = clock.now_us
        if not more:
            if nxt >= n:
                break
            clock.now_us = max(clock.now_us, wl["arrivals"][nxt])
    padded = sum(_padded_len(eng, len(p)) for p in wl["prompts"])
    return {"done_at": done_at,
            "tokens": {u: r.output for u, r in eng.results.items()},
            "prefill_compiles": eng.prefill_compiles(),
            "chunk_compiles": eng.chunk_compiles(),
            "levels": (eng.bucket_table.levels
                       if eng.bucket_table else []),
            "chunk": eng.chunk_tokens,
            "compile_stall_us": cost.compile_stall_us,
            "padded_tokens": padded}


def _latency_row(mode: str, family: str, wl, sim: Dict) -> Dict:
    lat = sim["done_at"] - wl["arrivals"]
    assert not np.isnan(lat).any(), \
        f"{family}/{mode}: unfinished requests"
    dl = ~wl["mono"]
    p50, p95 = np.percentile(lat, (50, 95))
    slo = float((sim["done_at"][dl] <= wl["deadlines"][dl]).mean())
    return {
        "section": "latency", "mode": mode, "family": family,
        "n_requests": len(lat),
        "p50_us": round(float(p50), 1),
        "p95_us": round(float(p95), 1),
        "deadline_slo_pct": round(100 * slo, 1),
        "compile_stall_us": round(sim["compile_stall_us"], 1),
    }


def _family_rows(family: str, arch: str, tiny: bool):
    """Calibrate one family, then serve the identical arrival process
    with its default and autotuned configs; returns (config rows,
    latency rows)."""
    from repro.core import calibrate

    bundle, params = _build(arch)
    vocab = bundle.cfg.vocab
    n = 12 if tiny else N_REQUESTS
    n_calib = 40 if tiny else N_CALIB
    # moe has no chunked fast path (typed UnsupportedFamilyError), so
    # its chunk search space is {0}; every other family here sweeps the
    # usual candidates
    chunks = (0,) if family == "moe" else CHUNKS

    # 1. the length model: the SAME 80/20 short/long mix the PR-4
    # arrival process serves (costs are placeholders — only the
    # lengths feed calibration)
    cwl = _engine_workload(np.random.default_rng(SEED + 4), n_calib,
                           vocab, 1.0, 1.0)
    lengths = [len(p) for p in cwl["prompts"]]
    profile = calibrate(bundle, params, lengths, cache_len=CACHE_LEN,
                        seed=SEED, candidate_levels=CANDIDATES,
                        chunk_candidates=chunks)
    decode_us = _measure_decode_us(bundle, params)

    # 2. the served workload: measured costs set arrivals & deadlines.
    # The PR-4 generator spaces arrivals by decode cost alone; here the
    # horizon additionally amortizes the DEFAULT config's one-time
    # compile stalls, so the process outlives the cold start and SLO
    # attainment reflects how quickly each config gets warm — not just
    # that both start cold.
    short_us = next(c.step_us for c in profile.bucket_costs
                    if c.length == 8)
    wl = _engine_workload(np.random.default_rng(SEED + 5), n, vocab,
                          decode_us, short_us)
    by_len = {c.length: c for c in profile.bucket_costs}
    from repro.core import BucketTable
    default_tbl = BucketTable(min_bucket=8, max_bucket=CACHE_LEN)
    default_hit = {default_tbl.fit(max(len(p) - 1, 1))
                   for p in wl["prompts"] if len(p) > 1}
    stall = sum(by_len[l].trace_overhead_us
                for l in default_hit if l in by_len)
    spacing = stall / n + 3.0 * decode_us
    rng = np.random.default_rng(SEED + 6)
    wl["arrivals"] = np.cumsum(rng.exponential(spacing, n))
    service = short_us + 4 * decode_us
    wl["deadlines"] = np.where(
        wl["mono"], np.inf, wl["arrivals"] + 4.0 * service)

    sims = {"default": _sim(bundle, params, wl, profile, decode_us,
                            tuned=False),
            "autotuned": _sim(bundle, params, wl, profile, decode_us,
                              tuned=True)}
    match = sims["autotuned"]["tokens"] == sims["default"]["tokens"]
    assert match, \
        f"{family}: autotuned config changed the decoded tokens"

    rows: List[Dict] = []
    for mode, sim in sims.items():
        rows.append({
            "section": "config", "mode": mode, "family": family,
            "bucket_levels": ",".join(map(str, sim["levels"])),
            "prefill_chunk": sim["chunk"],
            "prefill_compiles": sim["prefill_compiles"],
            # the profile's compile prediction assumes its bucket table
            # is applied — only true of an autotuned bucketed family
            "predicted_compiles": (
                profile.predicted_compiles
                if mode == "autotuned" and sim["levels"] else -1),
            "padded_tokens": sim["padded_tokens"],
            "tokens_match_default": bool(match),
        })
    lrows = [_latency_row(mode, family, wl, sim)
             for mode, sim in sims.items()]
    return rows, lrows


def run(tiny: bool = False) -> List[Dict]:
    """Calibrate and serve every family in the matrix with its default
    and autotuned configs; emit ``BENCH_autotune.json`` unless
    ``tiny``."""
    rows: List[Dict] = []
    lrows: List[Dict] = []
    for family, arch in FAMILIES:
        r, l = _family_rows(family, arch, tiny)
        rows += r
        lrows += l
    print_table("Autotuned vs default config, full family matrix "
                "(solved bucket table + chunk; compile counts)", rows)
    print_table("Arrival-process completion latency on measured costs "
                "(cold compile stalls included)", lrows)
    all_rows = rows + lrows
    if not tiny:
        save_result("BENCH_autotune", all_rows, seed=SEED)
    return all_rows


if __name__ == "__main__":
    run(tiny="--tiny" in sys.argv[1:])
