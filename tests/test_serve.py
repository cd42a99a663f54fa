"""StreamingServer lifecycle + mid-stream preemption regression
(launch/serve.py, docs/STREAMING.md).

The server wraps ONE engine on a dedicated loop thread: start →
submit/stream → shutdown.  These tests pin the lifecycle contract
(double start refused, duplicate uids refused, submit-after-shutdown
refused, shutdown unblocks abandoned streams), prove the streamed
tokens are bit-identical to a synchronous batch run of the same
workload, and regression-test the exactly-once emission contract when
a request is preempted and restored MID-STREAM — both via a forced
engine-level evict and via the EDF displacement policy running under
the live server loop.
"""

import queue

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.executor import jit_cache_size
from repro.launch.serve import StreamingServer
from repro.models import get_model
from repro.serving import Request, ServingEngine

ARCH = "qwen3-32b"
CACHE_LEN = 64
N_NEW = 6

_SETUP = {}


def _setup():
    if not _SETUP:
        cfg = get_config(ARCH, reduced=True)
        m = get_model(cfg)
        _SETUP["v"] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _SETUP["v"]


def _mk_engine(**kw):
    cfg, m, params = _setup()
    kw.setdefault("max_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("prefill_buckets", False)
    return ServingEngine(m, params, **kw)


def _prompts(n, seed=7):
    cfg, _, _ = _setup()
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab - 2,
                         int(rng.integers(6, 14))).astype(np.int32)
            for _ in range(n)]


def _events_by_uid(events):
    per = {}
    for ev in events:
        per.setdefault(ev.uid, []).append(ev)
    return per


def _assert_exactly_once(evs, expect_tokens, uid):
    """The callback ordering contract for one request's event list."""
    assert [e.index for e in evs] == list(range(len(evs))), uid
    assert [e.token for e in evs] == list(expect_tokens), uid
    assert [e.final for e in evs] == \
        [False] * (len(evs) - 1) + [True], uid
    ts = [e.t_us for e in evs]
    assert ts == sorted(ts), uid


def test_server_lifecycle():
    """start → submit → stream → shutdown, with every misuse refused
    loudly: double start, duplicate uid, submit after shutdown."""
    server = StreamingServer(_mk_engine(overlap=True)).start()
    assert server.running
    with pytest.raises(RuntimeError):
        server.start()
    prompt = _prompts(1)[0]
    uid = server.submit(prompt, max_new_tokens=N_NEW)
    with pytest.raises(ValueError):
        server.submit(prompt, max_new_tokens=N_NEW, uid=uid)
    evs = list(server.stream(uid))
    assert len(evs) == N_NEW
    _assert_exactly_once(evs, server.result(uid).output, uid)
    assert server.result(uid).done
    server.shutdown()
    assert not server.running
    with pytest.raises(RuntimeError):
        server.submit(prompt)
    server.shutdown()  # idempotent


def test_streamed_tokens_match_sync_batch():
    """The overlapped server streams the SAME tokens a synchronous
    batch engine decodes for the same workload, with the overlap
    engine's decode still a single jitted program."""
    prompts = _prompts(4)
    sync = _mk_engine(overlap=False)
    for uid, toks in enumerate(prompts):
        sync.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW))
    base = {uid: res.output for uid, res in sync.run().items()}

    eng = _mk_engine(overlap=True)
    server = StreamingServer(eng).start()
    uids = [server.submit(toks, max_new_tokens=N_NEW, uid=uid)
            for uid, toks in enumerate(prompts)]
    streamed = {uid: [ev.token for ev in server.stream(uid)]
                for uid in uids}
    server.shutdown()
    assert streamed == base
    assert jit_cache_size(eng._decode) == 1


def test_shutdown_unblocks_unfinished_stream():
    """A consumer waiting on a request the server will never finish
    gets a RuntimeError at shutdown, not a hang."""
    server = StreamingServer(_mk_engine(overlap=True)).start()
    uid = server.submit(_prompts(1)[0], max_new_tokens=50)
    server.shutdown()
    res = server.result(uid)
    if res is not None and res.done:
        pytest.skip("request finished before shutdown landed")
    with pytest.raises(RuntimeError, match="shut down"):
        list(server.stream(uid, timeout=5.0))


def test_stream_timeout_raises_empty():
    """stream() surfaces a stalled request as queue.Empty after its
    timeout instead of blocking forever."""
    server = StreamingServer(_mk_engine(overlap=True)).start()
    with server._lock:
        server._streams[99] = queue.Queue()  # uid the engine never saw
    with pytest.raises(queue.Empty):
        next(iter(server.stream(99, timeout=0.05)))
    server.shutdown()


def test_midstream_forced_evict_no_dup_no_drop():
    """THE preemption regression: a request evicted and restored while
    its stream is live must emit every token exactly once — no
    re-emission of the pre-evict prefix, no dropped tail — and match
    the never-preempted sync baseline bit for bit."""
    prompts = _prompts(4)
    sync = _mk_engine(overlap=False)
    for uid, toks in enumerate(prompts):
        sync.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW))
    base = {uid: res.output for uid, res in sync.run().items()}

    events = []
    eng = _mk_engine(overlap=True, on_token=events.append)
    for uid, toks in enumerate(prompts):
        eng.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW))
    evicted = False
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 500
        if not evicted and steps >= 3:
            eng.drain()  # quiesce before checkpoint surgery
            victim = next((s for s in range(eng.max_slots)
                           if eng.active[s]), None)
            if victim is not None:
                eng._evict(victim)
                evicted = True
    assert evicted
    assert sum(r.preemptions for r in eng.results.values()) >= 1
    outs = {uid: res.output for uid, res in eng.results.items()}
    assert outs == base
    per = _events_by_uid(events)
    assert sorted(per) == sorted(outs)
    for uid, evs in per.items():
        _assert_exactly_once(evs, outs[uid], uid)
    assert jit_cache_size(eng._decode) == 1


def test_midstream_displacement_under_live_server():
    """The same exactly-once guarantee through the displacement policy
    with the server loop running: a tight-deadline arrival displaces
    the lone running request mid-stream, and both streams still see
    contiguous indices and their full budgets."""
    events = []
    eng = _mk_engine(overlap=True, max_slots=1, policy="edf",
                     preempt="edf-displace")
    server = StreamingServer(eng)
    # the server claimed on_token; tee every event into our collector
    # on its way to the per-uid stream queues
    fanout = eng.on_token
    eng.on_token = lambda ev: (events.append(ev), fanout(ev))
    server.start()
    p0, p1 = _prompts(2)
    uid0 = server.submit(p0, max_new_tokens=10)  # no deadline: displaceable
    g0 = server.stream(uid0)
    next(g0)  # wait until uid0 is decoding mid-stream
    uid1 = server.submit(p1, max_new_tokens=4, uid=101,
                         deadline_us=1)  # urgent: forces displacement
    t1 = [ev.token for ev in server.stream(uid1)]
    t0_rest = [ev.token for ev in g0]
    server.shutdown()
    res0, res1 = server.result(uid0), server.result(uid1)
    assert res0.done and res1.done
    assert res0.preemptions >= 1, "displacement never fired"
    assert len(t1) == 4 and t1 == res1.output
    assert len(t0_rest) == 9  # the 10-token budget minus next(g0)
    per = _events_by_uid(events)
    _assert_exactly_once(per[uid0], res0.output, uid0)
    _assert_exactly_once(per[uid1], res1.output, uid1)


class _FailingEngine:
    """Engine stand-in whose ``step()`` raises once work arrives."""

    def __init__(self):
        from repro.serving import default_clock
        self.on_token = None
        self.results = {}
        self.clock = default_clock
        self._work = False

    def submit(self, req):
        self._work = True

    def step(self):
        if self._work:
            raise ValueError("engine step failed")
        return False

    def drain(self):
        pass


def test_engine_error_surfaces_from_stream_and_shutdown():
    """An exception on the loop thread reaches the consumer as itself,
    at once — not as a ``queue.Empty`` after the stream timeout."""
    import time
    server = StreamingServer(_FailingEngine()).start()
    uid = server.submit(np.arange(4, dtype=np.int32))
    t0 = time.monotonic()
    with pytest.raises(ValueError, match="engine step failed"):
        list(server.stream(uid, timeout=60.0))
    assert time.monotonic() - t0 < 1.0
    with pytest.raises(ValueError, match="engine step failed"):
        server.submit(np.arange(4, dtype=np.int32))
    with pytest.raises(ValueError, match="engine step failed"):
        server.shutdown()
