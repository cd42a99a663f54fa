"""One run of one cell: set-up, a measured window, the check of what
the window served, and the record the metric readers read.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/``, its
traffic mix in ``bench/traffic/``, the weights, reference and work of
its configuration's family in ``bench/families/<family>.py``, and each
of its metrics as a reader in ``bench/metrics/<name>.py``.  The
configuration's ``engine`` block goes to ``ServingEngine`` as it
stands (slots, cache length, overlap, and any other engine setting: a
``kv_block``, a ``weight_dtype``), and a cell on more than one chip
runs one engine sharded over a ``make_serving_mesh(chips)`` mesh.  A
new cell adds files and entries and edits nothing here.

The window drives the served path: ``launch/serve.StreamingServer``
over a ``ServingEngine`` with overlapped decode.  The client side is
the benchmark's own: a generator submits the traffic and a receiver
thread per request timestamps every ``StreamEvent`` as it is taken
from ``stream(uid)``.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import loadgen, reference, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

# a stream that sends nothing for this long is a hung server
STREAM_TIMEOUT_S = 120.0
# the traced part of a --trace 1 run: it opens this far into the window
# and lasts TRACE_S (a few hundred decode steps; a whole window's trace
# would be too large to read back inside a run's time limit)
TRACE_LEAD = 0.4
TRACE_S = 5.0
# the spans that label the device's idle gaps in the breakdown
SPANS = ("engine.step", "server.submit", "client.receive")
# the decode-attention kernel's operations in a device trace
DECODE_KERNEL = r"^decode_attention_pallas"


# ---------------------------------------------------------------------------
# the spec: BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """A cell with everything its files say.  ``conf`` is the
    configuration as run: the file's published keys with its ``as_run``
    block laid over them."""

    name: str
    chips: int
    conf: Dict[str, Any]        # the configuration file, as run
    traffic: Dict[str, Any]     # the traffic file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def family(self):
        return family(self.conf["family"])

    @property
    def dims(self):
        return self.family.Dims.from_config(self.conf)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    conf = json.loads((root / conf_entry["file"]).read_text())
    conf.update(conf.get("as_run", {}))
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=w["chips"], conf=conf, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def reader(metric: str) -> Callable[["RunRecord"], Optional[float]]:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _module(BENCH / "metrics" / f"{metric}.py",
                   f"bench.metrics.{_ident(metric)}").read


_FAMILIES: Dict[str, Any] = {}


def family(name: str):
    """The module ``bench/families/<name>.py``."""
    if name not in _FAMILIES:
        _FAMILIES[name] = _module(BENCH / "families" / f"{name}.py",
                                  f"bench.families.{_ident(name)}")
    return _FAMILIES[name]


# ---------------------------------------------------------------------------
# the program under test, built from the configuration file
# ---------------------------------------------------------------------------

def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for ``conf``: the repo's entry for
    ``arch_id`` with every size the file states put in."""
    from repro.configs import get_config
    from repro.models.lm import padded_vocab
    fields = family(conf["family"]).PROGRAM_FIELDS
    cfg = dataclasses.replace(
        get_config(conf["arch_id"]),
        **{f: conf[k] for k, f in fields.items() if k in conf})
    if cfg.dh != conf.get("head_dim", cfg.d_model // cfg.n_heads):
        raise ValueError(f"head size {cfg.dh} is not the file's")
    if padded_vocab(cfg) != conf["vocab_padded"]:
        raise ValueError(f"the program pads the vocabulary to "
                         f"{padded_vocab(cfg)}, the file says "
                         f"{conf['vocab_padded']}")
    return cfg


def _same_layout(mine, program) -> None:
    """Raise unless the benchmark's weights have the program's tree,
    shapes and dtypes."""
    import jax
    a = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), mine)
    b = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), program)
    if a != b:
        raise ValueError(f"the benchmark's weights {a} are not laid out as "
                         f"the program's {b}")


def build_engine(cell: Cell, seed: int):
    """A ``ServingEngine`` over the cell's configuration, with the
    benchmark's seeded weights made on the device (straight into their
    shardings, on a mesh of the cell's chips when it has more than
    one)."""
    import jax
    from repro.models import get_model
    from repro.serving import ServingEngine
    cfg = model_config(cell.conf)
    bundle = get_model(cfg)
    mesh = shard = None
    if cell.chips > 1:
        from repro.distributed.sharding import param_sharding
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(cell.chips)

        def shard(shapes):
            return param_sharding(cfg, mesh, shapes, fsdp=False)
    dims = cell.dims
    fam = cell.family
    _same_layout(jax.eval_shape(lambda: fam.make_params(dims, seed)),
                 jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    params = fam.make_params(dims, seed, shard)
    return ServingEngine(bundle, params, seed=seed, mesh=mesh,
                         **cell.conf["engine"])


# ---------------------------------------------------------------------------
# compile log: every backend compile or persistent-cache load
# ---------------------------------------------------------------------------

class CompileLog:
    """Counts JAX's backend compiles; registered once per process."""

    _instance: Optional["CompileLog"] = None

    def __init__(self) -> None:
        self.events: List[tuple] = []

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            import jax
            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on)
        return cls._instance

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), secs))


# ---------------------------------------------------------------------------
# the record the metric readers read
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    """One request as the client saw it; times in seconds after the
    window opened."""

    uid: int
    prompt: np.ndarray
    max_new: int
    due: float
    submitted: Optional[float] = None
    recv: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Step:
    """One ``engine.step`` call as the benchmark's span saw it."""

    t0: float
    t1: float
    prefills: int
    decoded: bool
    active: int


@dataclasses.dataclass
class RunRecord:
    """What one run measured, for the metric readers."""

    cell: Cell
    seconds: float
    setup_s: float
    max_slots: int
    peaks: Any
    requests: List[Req]
    steps: List[Step]
    compiles_in_window: int
    trace: Optional[trace_reduce.Summary] = None
    trace_t0: float = 0.0       # the traced part, in window seconds
    trace_t1: float = 0.0

    @property
    def dims(self):
        return self.cell.dims

    @property
    def family(self):
        return self.cell.family

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and 0.0 <= t <= self.seconds

    def due_in_window(self) -> List[Req]:
        return [r for r in self.requests if r.due < self.seconds]


# ---------------------------------------------------------------------------
# the client side
# ---------------------------------------------------------------------------

class Client:
    """Submits the plan to a ``StreamingServer`` and timestamps every
    token where it is received.  ``start()`` opens the window."""

    def __init__(self, server, items: List[loadgen.Item],
                 traffic: Dict[str, Any], seconds: float):
        self.server = server
        self.items = items
        self.traffic = traffic
        self.seconds = seconds
        self.t0 = 0.0
        self.stop = threading.Event()
        self.reqs: Dict[int, Req] = {}
        self.threads: List[threading.Thread] = []
        self._lock = threading.Lock()

    def now(self) -> float:
        return time.monotonic() - self.t0

    def start(self) -> None:
        self.t0 = time.monotonic()
        if self.traffic["loop"] == "open":
            self._spawn(self._open_loop)
        else:
            by_client: Dict[int, List[loadgen.Item]] = {}
            for it in self.items:
                by_client.setdefault(it.client, []).append(it)
            for its in by_client.values():
                self._spawn(self._closed_client, its)

    def _spawn(self, fn, *args) -> None:
        t = threading.Thread(target=fn, args=args, daemon=True)
        with self._lock:
            self.threads.append(t)
        t.start()

    def _submit(self, it: loadgen.Item, due: float) -> Req:
        import jax
        req = Req(uid=it.uid, prompt=it.prompt, max_new=it.max_new,
                  due=due)
        with self._lock:
            self.reqs[it.uid] = req
        with jax.profiler.TraceAnnotation("server.submit"):
            self.server.submit(it.prompt, max_new_tokens=it.max_new,
                               uid=it.uid)
        req.submitted = self.now()
        return req

    def _receive(self, req: Req) -> None:
        import jax
        try:
            for ev in self.server.stream(req.uid,
                                         timeout=STREAM_TIMEOUT_S):
                t = self.now()
                with jax.profiler.TraceAnnotation("client.receive"):
                    req.recv.append(t)
                    req.tokens.append(int(ev.token))
                if ev.final:
                    req.done = True
        except RuntimeError as e:      # shut down with the request open
            if not self.stop.is_set():
                req.error = repr(e)
        except Exception as e:         # the engine died, or a hung stream
            req.error = repr(e)

    def _open_loop(self) -> None:
        for it in self.items:
            wait = it.due - self.now()
            if wait > 0 and self.stop.wait(wait):
                return
            try:
                req = self._submit(it, it.due)
            except RuntimeError:       # the server stopped as it was sent
                if self.stop.is_set():
                    return
                raise
            self._spawn(self._receive, req)

    def _closed_client(self, items: List[loadgen.Item]) -> None:
        for it in items:
            try:
                req = self._submit(it, self.now())
            except RuntimeError:
                if self.stop.is_set():
                    return
                raise
            self._receive(req)
            if self.stop.is_set():
                return

    def close(self) -> None:
        """Stop submitting; the server's shutdown ends open streams."""
        self.stop.set()

    def join(self) -> None:
        """Wait for every thread the client started."""
        while True:
            with self._lock:
                alive = [t for t in self.threads if t.is_alive()]
            if not alive:
                return
            for t in alive:
                t.join(STREAM_TIMEOUT_S)
                if t.is_alive():
                    raise RuntimeError("a client thread did not end")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _warm(server, engine, items: List[loadgen.Item]) -> int:
    """Serve one request for each prefill bucket the plan reaches
    (the longest planned prompt of that bucket), all slots' worth of
    decode, and wait until each has ended.  Returns the count."""
    by_bucket: Dict[int, int] = {}
    for it in items:
        n = len(it.prompt)
        b = engine.bucket_table.fit(n - 1) if engine.bucket_table else n
        by_bucket[b] = max(by_bucket.get(b, 0), n)
    uids = []
    for k, n in enumerate(sorted(by_bucket.values())):
        uid = 1_000_000_000 + k
        server.submit(np.zeros(n, np.int32) + 1, max_new_tokens=3, uid=uid)
        uids.append(uid)
    for uid in uids:
        for _ in server.stream(uid, timeout=STREAM_TIMEOUT_S):
            pass
    return len(uids)


def _spanned_steps(engine, steps: List[Step], clock: Callable[[], float]):
    """Wrap ``engine.step`` in the benchmark's ``engine.step`` span."""
    import jax
    inner = engine.step

    def step():
        t0 = clock()
        with jax.profiler.TraceAnnotation("engine.step"):
            more = inner()
        last = engine.last_step
        if last["decoded"] or last["prefill_tokens"]:
            steps.append(Step(t0=t0, t1=clock(),
                              prefills=len(last["prefill_tokens"]),
                              decoded=bool(last["decoded"]),
                              active=int(engine.active.sum())))
        return more

    engine.step = step


def _device_peak(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, peaks: Any,
        tamper: Optional[Callable[[Any], None]] = None,
        control: bool = False, compare: bool = True,
        keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """One run of ``cell``.  ``t_start`` is the process's start on the
    monotonic clock (set-up counts from it).  The rest serve the tests
    and ``bench/probe.py``: ``tamper(engine)`` breaks the timed path
    before the window, ``control`` puts the lower-precision control in
    the program's place in the check, ``compare=False`` skips it, and
    ``keep_trace`` names a directory that keeps the raw trace.
    Returns the result line's fields and the record."""
    import jax
    from repro.launch.serve import StreamingServer

    log = CompileLog.get()
    dims = cell.dims
    items = loadgen.plan(cell.traffic, seed, seconds, dims.vocab)
    engine = build_engine(cell, seed)
    jax.block_until_ready(engine.params)
    if tamper is not None:
        tamper(engine)
    server = StreamingServer(engine).start()
    _warm(server, engine, items)
    steps: List[Step] = []
    client = Client(server, items, cell.traffic, seconds)
    _spanned_steps(engine, steps, client.now)
    n_compiles = len(log.events)
    # the set-up's objects (JAX's, the weights' handles, the plan) never
    # become garbage: keep the collector's full passes off them, so a
    # pass inside the window scans only what the window made
    gc.collect()
    gc.freeze()
    client.start()
    setup_s = client.t0 - t_start
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    trace_path = keep_trace or (trace_dir.name if trace else None)
    t_trace = (0.0, 0.0)
    try:
        if trace:
            lead = TRACE_LEAD * seconds
            span = min(TRACE_S, seconds - lead)
            time.sleep(max(0.0, lead - client.now()))
            jax.profiler.start_trace(trace_path)
            t0 = client.now()
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(max(0.0, lead + span - client.now()))
            t_trace = (t0, client.now())
            jax.profiler.stop_trace()
        time.sleep(max(0.0, seconds - client.now()))
    finally:
        client.close()
        compiles = len(log.events) - n_compiles
        server.shutdown()
        client.join()
        gc.unfreeze()
    memory_peak = _device_peak(cell.chips)
    summary = None
    if trace:
        xplane = sorted(glob.glob(f"{trace_path}/**/*.xplane.pb",
                                  recursive=True))
        summary = trace_reduce.reduce(xplane[-1], spans=SPANS,
                                      marks={"decode": DECODE_KERNEL})
        trace_dir.cleanup()
    rec = RunRecord(cell=cell, seconds=seconds, setup_s=setup_s,
                    max_slots=engine.max_slots, peaks=peaks,
                    requests=sorted(client.reqs.values(),
                                    key=lambda r: r.uid),
                    steps=steps, compiles_in_window=compiles,
                    trace=summary, trace_t0=t_trace[0],
                    trace_t1=t_trace[1])
    # free the program's state before the reference runs
    del server, engine, client
    gc.collect()
    check = (reference.check(cell, seed, rec, control=control) if compare
             else reference.Check(False, {}))
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in getattr(cell, kind):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = [r for r in rec.requests
                 if r.submitted is not None and r.submitted <= seconds]
    return {
        "correct": check.correct,
        "attempted": len(attempted),
        "failed": sum(r.error is not None for r in attempted),
        "metrics": metrics,
        "memory_peak_bytes": memory_peak,
        "compiles_in_window": compiles,
        "check": check,
        "record": rec,
    }
