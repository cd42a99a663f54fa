"""The serving path's own observability (docs/STREAMING.md §5): the
engine-clock stamps on ``RequestResult`` (arrival, admission, first
token), the per-tick ``readback_s`` / ``host_s`` counters in
``engine.last_step``, and the profiler spans the engine and
``StreamingServer`` write into a ``jax.profiler`` trace — nested under
a caller's outer span, tied together by ``uid``, one per layer boundary
and never one per token."""

import gc
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import StreamingServer
from repro.models import get_model
from repro.serving import ServingEngine, default_clock

ARCH = "qwen3-32b"
CACHE_LEN = 64
N_NEW = 4

ENGINE_SPANS = ("engine.admit", "engine.prefill", "engine.decode",
                "engine.readback", "engine.emit")
SERVER_SPANS = ("server.inbox", "server.idle")

_SETUP = {}


def _setup():
    if not _SETUP:
        cfg = get_config(ARCH, reduced=True)
        m = get_model(cfg)
        _SETUP["v"] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _SETUP["v"]


def _mk_engine(**kw):
    _, m, params = _setup()
    kw.setdefault("max_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("prefill_buckets", False)
    return ServingEngine(m, params, **kw)


def _prompts(n, seed=3):
    cfg, _, _ = _setup()
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab - 2,
                         int(rng.integers(6, 14))).astype(np.int32)
            for _ in range(n)]


def _serve(server, prompts):
    """Submit every prompt, then read every stream to its end; returns
    the uids and the engine clock around each submit."""
    uids, around = [], {}
    for p in prompts:
        before = default_clock()
        uid = server.submit(p, max_new_tokens=N_NEW)
        around[uid] = (before, default_clock())
        uids.append(uid)
    for uid in uids:
        assert len(list(server.stream(uid, timeout=60))) == N_NEW
    return uids, around


@pytest.mark.parametrize("overlap", [True, False])
def test_arrival_admission_first_token_in_order(overlap):
    """Through a StreamingServer with more requests than slots (so some
    queue): ``arrival_us`` is stamped at ``submit`` and copied onto the
    result, and arrival <= admission <= first token."""
    eng = _mk_engine(overlap=overlap)
    server = StreamingServer(eng).start()
    try:
        uids, around = _serve(server, _prompts(5))
    finally:
        server.shutdown()
    for uid in uids:
        res = eng.results[uid]
        lo, hi = around[uid]
        assert lo <= res.arrival_us <= hi
        assert res.arrival_us <= res.admitted_us <= res.first_token_us
    # five requests through two slots: the last waited for one
    waits = [eng.results[u].admitted_us - eng.results[u].arrival_us
             for u in uids]
    assert max(waits) > 0


@pytest.mark.parametrize("overlap", [True, False])
def test_admission_stamp_survives_preempt_and_restore(overlap):
    eng = _mk_engine(overlap=overlap, max_slots=1)
    from repro.serving import Request
    eng.submit(Request(uid=0, tokens=_prompts(1)[0], max_new_tokens=6))
    eng.step()
    eng.step()
    res = eng.results[0]
    admitted = res.admitted_us
    assert admitted is not None
    eng._evict(0)
    time.sleep(0.002)
    eng.run()
    assert res.preemptions == 1 and res.done
    assert res.admitted_us == admitted
    assert res.admitted_us <= res.first_token_us


def test_readback_counter_marks_the_ticks_that_read_back():
    """Overlapped: a tick blocks on the device's tokens only when a
    step was in flight before it; the first decode tick dispatches
    without reading back.  Sync: every decoding tick reads back.
    ``host_s`` is the rest of the tick, never negative."""
    from repro.serving import Request
    for overlap in (True, False):
        eng = _mk_engine(overlap=overlap)
        for uid, p in enumerate(_prompts(3)):
            eng.submit(Request(uid=uid, tokens=p, max_new_tokens=N_NEW))
        ticks = []
        more = True
        while more:
            in_flight = eng._inflight is not None
            t0 = time.perf_counter()
            more = eng.step()
            wall = time.perf_counter() - t0
            ticks.append((in_flight, dict(eng.last_step), wall))
        assert len(ticks) > N_NEW
        for k, (in_flight, last, wall) in enumerate(ticks):
            assert last["host_s"] >= 0
            assert last["host_s"] + last["readback_s"] <= wall
            if overlap:
                assert (last["readback_s"] > 0) == in_flight, k
            else:
                assert (last["readback_s"] > 0) == last["decoded"], k
        assert not ticks[0][0] and ticks[0][1]["decoded"]


def _trace_events(path):
    """{thread line: [(name, start_ns, end_ns, stats)]} of the host;
    stats are read for the spans that carry ids."""
    from jax.profiler import ProfileData
    with_ids = ("engine.admit", "engine.prefill", "gc")
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats) if e.name in with_ids else {})
                   for e in line.events]
            if evs:
                out[(line.name, id(line))] = evs
    return out


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.mark.parametrize("overlap", [True, False])
def test_profiler_trace_holds_every_span(tmp_path, overlap):
    """A CPU profiler trace of a live server: every span of the table in
    docs/STREAMING.md §5, the engine's nested under the caller's outer
    ``engine.step`` span on the loop thread, ``uid`` on admissions,
    a ``gc`` span per forced collection; no span per token; and the gc
    hook is gone after ``shutdown()``."""
    eng = _mk_engine(overlap=overlap, prefill_buckets=None)
    inner = eng.step

    def spanned_step():
        with jax.profiler.TraceAnnotation("engine.step"):
            return inner()

    eng.step = spanned_step
    server = StreamingServer(eng).start()
    _serve(server, _prompts(4))                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        uids, _ = _serve(server, _prompts(4))
        gc.collect()
        time.sleep(0.02)                         # the loop naps, idle
    finally:
        jax.profiler.stop_trace()
        server.shutdown()
    assert server._on_gc not in gc.callbacks

    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    lines = _trace_events(path)
    loop = [evs for evs in lines.values()
            if any(e[0] == "engine.step" for e in evs)]
    assert len(loop) == 1
    evs = loop[0]
    steps = [e for e in evs if e[0] == "engine.step"]
    for name in ENGINE_SPANS + SERVER_SPANS:
        assert any(e[0] == name for e in evs), name
    for e in evs:
        if e[0] in ENGINE_SPANS:
            assert any(_inside(e, s) for s in steps), e
    admits = [e for e in evs if e[0] == "engine.admit"]
    assert {e[3]["uid"] for e in admits} == set(uids)
    for p in (e for e in evs if e[0] == "engine.prefill"):
        assert p[3]["uid"] in uids and p[3]["tokens"] > 0
        assert any(_inside(p, a) and a[3]["uid"] == p[3]["uid"]
                   for a in admits)
    # one emission span per tick at most, though a tick emits a token
    # for each busy slot
    emits = [e for e in evs if e[0] == "engine.emit"]
    for s in steps:
        assert sum(_inside(e, s) for e in emits) <= 1
    assert len(emits) < len(uids) * N_NEW
    gcs = [e for evs in lines.values() for e in evs if e[0] == "gc"]
    assert any(e[3].get("generation") == 2 for e in gcs)


def _moe_engine(**kw):
    cfg = get_config("deepseek-moe-16b", reduced=True)
    m = get_model(cfg)
    return cfg, ServingEngine(m, m.init(jax.random.PRNGKey(1)), max_slots=2,
                              cache_len=CACHE_LEN, **kw)


def test_moe_row_counters_follow_the_programs_shapes():
    """``expert_rows``: every expert's dropless queue over the tokens of
    each program's shape (the padded prefill bucket, all decode slots);
    ``routed_rows``: the live tokens times top_k; both over the MoE
    layers.  A dense engine's ticks count none."""
    from repro.serving import Request
    cfg, eng = _moe_engine()
    moe_layers = cfg.n_layers - 1
    prompts = _prompts(3)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, tokens=p, max_new_tokens=N_NEW))
    ticks = []
    while eng.step():
        ticks.append(dict(eng.last_step))
    ticks.append(dict(eng.last_step))
    for t in ticks:
        live = t["processed"] if t["decoded"] else 0
        prefill = sum(t["prefill_tokens"])
        assert t["expert_rows"] == moe_layers * cfg.n_experts * (
            prefill + (eng.max_slots if t["decoded"] else 0))
        assert t["routed_rows"] <= t["expert_rows"]
    pre = sum(len(p) - 1 for p in prompts)
    dec = sum(t["processed"] for t in ticks)
    assert sum(t["routed_rows"] for t in ticks) == \
        moe_layers * cfg.top_k * (pre + dec)
    dense = _mk_engine()
    dense.submit(Request(uid=0, tokens=prompts[0], max_new_tokens=2))
    dense.step()
    assert dense.last_step["expert_rows"] == dense.last_step[
        "routed_rows"] == 0


@pytest.mark.parametrize("overlap", [True, False])
def test_moe_row_counters_read_nothing_back(monkeypatch, overlap):
    """The counters are host arithmetic: a moe engine's tick reads the
    device back only where it reads a step's tokens (every way numpy
    or Python can read a device array is counted)."""
    from jax._src.array import ArrayImpl
    from repro.serving import Request
    _, eng = _moe_engine(overlap=overlap)
    for uid, p in enumerate(_prompts(3)):
        eng.submit(Request(uid=uid, tokens=p, max_new_tokens=N_NEW))
    eng.step()                        # compile the first programs
    reads = []

    def counted(name):
        inner = getattr(ArrayImpl, name)

        def read(self, *a, **kw):
            reads.append(name)
            return inner(self, *a, **kw)
        monkeypatch.setattr(ArrayImpl, name, read)

    for name in ("__buffer__", "__array__", "__int__", "__index__",
                 "__float__", "__bool__", "item", "tolist"):
        counted(name)
    more = True
    while more:
        reads.clear()
        more = eng.step()
        last = eng.last_step
        read_back = last["readback_s"] > 0
        assert last["expert_rows"] > 0 or not (last["decoded"]
                                               or last["prefill_tokens"])
        # sync mode reads the logits, lengths and tokens of its step
        assert len(reads) == (0 if not read_back else 1 if overlap else 3)
