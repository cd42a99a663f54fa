"""Serving driver: ``python -m repro.launch.serve --arch qwen3-32b
--reduced --requests 8``.

Batched-request serving through the ServingEngine (continuous batching,
arena-planned KV).  The paper is an inference framework, so this is the
end-to-end driver: submit a workload of prompts, stream them through
fixed decode slots, report latency/throughput stats.

Two modes:

  * default — batch: submit everything, ``eng.run()``, print per-request
    latency and the throughput summary at the end.
  * ``--stream`` — interactive: a ``StreamingServer`` drives the engine
    (overlapped decode where the family supports it) on a background
    thread and every token is printed the moment the host learns it,
    with per-request TTFT / mean-ITL lines (docs/STREAMING.md).  This
    is the minimal serving front-end ``examples/streaming_client.py``
    builds its interactive demo on.
"""

from __future__ import annotations

import argparse
import gc
import json
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.models import get_model
from repro.serving import (Request, ServingEngine, STREAMING_FAMILIES,
                           StreamEvent)


class StreamingServer:
    """Minimal streaming front-end over one ``ServingEngine``:
    ``start()`` → ``submit()`` / ``stream()`` → ``shutdown()``.

    The engine runs on ONE dedicated background thread (engines are
    not thread-safe; the thread owns every engine call).  ``submit``
    hands prompts over a lock-protected inbox the loop drains before
    each engine tick, and the engine's ``on_token`` callback — firing
    on the loop thread — fans each ``StreamEvent`` out to a per-uid
    ``queue.Queue`` as it is emitted.  Consumers iterate ``stream(uid)``
    from any thread and see that request's tokens in order, exactly
    once, ending with the ``final`` event; the engine's own emission
    contract (docs/STREAMING.md) guarantees that holds across
    preemption and restore.

    ``shutdown()`` stops the loop after settling any in-flight
    overlapped step (``engine.drain()``), then unblocks every open
    stream with a ``None`` sentinel so no consumer hangs on a request
    the server will never finish.  If the engine raises on the loop
    thread, the loop records the exception and unblocks every open
    stream at once; ``stream()``, ``submit()`` and ``shutdown()`` then
    re-raise it.

    For a profiler trace (``jax.profiler.start_trace`` around a live
    server) the loop thread writes ``server.inbox`` and ``server.idle``
    spans around the engine's own, and each garbage collection in the
    process is a ``gc`` span from ``start()`` to ``shutdown()``
    (docs/STREAMING.md §5).  ``submit`` stamps ``arrival_us``, so a
    request's queue wait counts from its hand-over, not from the tick
    that drains the inbox."""

    def __init__(self, engine: ServingEngine, *, idle_s: float = 0.001):
        self.engine = engine
        engine.on_token = self._on_token
        self._idle_s = idle_s
        self._inbox: List[Request] = []
        self._lock = threading.Lock()
        self._streams: Dict[int, "queue.Queue"] = {}
        self._next_uid = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None
        self._gc_span: Optional[jax.profiler.TraceAnnotation] = None

    @property
    def running(self) -> bool:
        """True between ``start()`` and ``shutdown()``."""
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "StreamingServer":
        """Spawn the engine loop thread (idempotent error: a second
        start while running is refused)."""
        if self.running:
            raise RuntimeError("server already running")
        self._stop.clear()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._loop,
                                        name="serving-loop", daemon=True)
        self._thread.start()
        return self

    def submit(self, tokens: np.ndarray, *, max_new_tokens: int = 16,
               uid: Optional[int] = None, **req_kw: Any) -> int:
        """Enqueue one prompt; returns the uid to ``stream()`` on.
        Extra keywords (priority, deadline_us, tenant, extras, …) pass
        through to ``Request``."""
        if self._error is not None:
            raise self._error
        if not self.running:
            raise RuntimeError("server is not running")
        with self._lock:
            if uid is None:
                uid = self._next_uid
            self._next_uid = max(self._next_uid, uid + 1)
            if uid in self._streams:
                raise ValueError(f"uid {uid} already submitted")
            self._streams[uid] = queue.Queue()
            req_kw.setdefault("arrival_us", self.engine.clock())
            self._inbox.append(Request(
                uid=uid, tokens=np.asarray(tokens, np.int32),
                max_new_tokens=max_new_tokens, **req_kw))
        return uid

    def stream(self, uid: int, *,
               timeout: float = 60.0) -> Iterator[StreamEvent]:
        """Yield ``uid``'s StreamEvents in order until its ``final``
        token.  Raises ``queue.Empty`` if no token arrives within
        ``timeout`` seconds, the engine's own exception if the loop
        thread died of one, and ``RuntimeError`` if the server shuts
        down with the request unfinished."""
        q = self._streams[uid]
        while True:
            ev = q.get(timeout=timeout)
            if ev is None:
                if self._error is not None:
                    raise self._error
                raise RuntimeError(
                    f"server shut down before request {uid} finished")
            yield ev
            if ev.final:
                return

    def result(self, uid: int):
        """The accumulated ``RequestResult`` for ``uid`` (None until
        the engine has seen the submission)."""
        return self.engine.results.get(uid)

    def shutdown(self, timeout: float = 30.0) -> None:
        """Stop the loop thread, drain any in-flight step, and unblock
        every open stream.  Safe to call twice.  Re-raises the
        engine's exception if the loop thread died of one."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._error is not None:
            raise self._error
        self.engine.drain()
        self._unblock_streams()

    def _unblock_streams(self) -> None:
        """Put the ``None`` sentinel on every unfinished stream."""
        with self._lock:
            for uid, q in self._streams.items():
                res = self.engine.results.get(uid)
                if res is None or not res.done:
                    q.put(None)

    # -- loop thread ----------------------------------------------------

    def _on_token(self, ev: StreamEvent) -> None:
        self._streams[ev.uid].put(ev)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """A ``gc`` span from each collection's start to its stop (a
        collection runs start to stop on one thread, never nested)."""
        if phase == "start":
            self._gc_span = jax.profiler.TraceAnnotation(
                "gc", generation=info["generation"])
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                with jax.profiler.TraceAnnotation("server.inbox"):
                    with self._lock:
                        pending, self._inbox = self._inbox, []
                    for req in pending:
                        self.engine.submit(req)
                if not self.engine.step():
                    # idle: engine fully drained — nap until new work
                    with jax.profiler.TraceAnnotation("server.idle"):
                        self._stop.wait(self._idle_s)
            self.engine.drain()
        except Exception as e:   # surfaced by stream()/submit()/shutdown()
            self._error = e
            self._unblock_streams()


def init_params(bundle, key, mesh=None) -> Any:
    """The model's weights, made on the device by one jitted program.
    With a ``mesh`` they are made straight into the engine's param
    shardings, so no single device ever holds the whole model."""
    if mesh is None:
        return jax.jit(bundle.init)(key)
    from repro.distributed.sharding import param_sharding
    shapes = jax.eval_shape(bundle.init, key)
    shard = param_sharding(bundle.cfg, mesh, shapes, fsdp=False)
    return jax.jit(bundle.init, out_shardings=shard)(key)


def build_engine(cfg, *, seed: int = 0, mesh=None,
                 **engine_kw: Any) -> ServingEngine:
    """One engine for ``cfg`` with seeded random weights; ``engine_kw``
    go to ``ServingEngine``."""
    bundle = get_model(cfg)
    params = init_params(bundle, jax.random.PRNGKey(seed), mesh)
    return ServingEngine(bundle, params, seed=seed, mesh=mesh,
                         **engine_kw)


def _build_engine(args) -> ServingEngine:
    """One engine from the CLI knobs; ``--stream`` turns overlapped
    decode on for families the async loop supports."""
    cfg = get_config(args.arch, reduced=args.reduced)
    overlap = args.stream and cfg.family in STREAMING_FAMILIES
    return build_engine(cfg, seed=args.seed, max_slots=args.slots,
                        cache_len=args.cache_len, overlap=overlap)


def _workload(cfg, args) -> List[Dict[str, Any]]:
    """The demo prompt mix: random prompts (plus the vision/audio
    extras multimodal families need)."""
    rng = np.random.default_rng(args.seed)
    reqs = []
    for uid in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2,
                                args.prompt_len + 1))
        extras = None
        if cfg.family == "vlm":
            extras = {"vision": rng.normal(
                0, 1, (cfg.n_vision_tokens, cfg.d_vision)
            ).astype(np.float32)}
        elif cfg.family == "audio":
            extras = {"frames": rng.normal(
                0, 0.1, (cfg.n_audio_ctx, cfg.d_model)
            ).astype(np.float32)}
        reqs.append(dict(
            uid=uid,
            tokens=rng.integers(0, cfg.vocab - 2, plen).astype(np.int32),
            max_new_tokens=args.max_new, extras=extras))
    return reqs


def _serve_stream(eng: ServingEngine, cfg, args) -> None:
    """``--stream`` mode: per-token delivery through StreamingServer,
    TTFT / mean-ITL per request."""
    from repro.serving import default_clock
    server = StreamingServer(eng).start()
    t0 = time.time()
    uids, t_sub = [], {}
    for r in _workload(cfg, args):
        t_sub[r["uid"]] = default_clock()
        uids.append(server.submit(
            r["tokens"], max_new_tokens=r["max_new_tokens"],
            uid=r["uid"], extras=r["extras"]))
    total = 0
    for uid in uids:
        stamps = []
        toks = []
        for ev in server.stream(uid):
            stamps.append(ev.t_us)
            toks.append(ev.token)
        total += len(toks)
        ttft_ms = (stamps[0] - t_sub[uid]) / 1e3
        itl = np.diff(stamps) / 1e3 if len(stamps) > 1 else np.zeros(1)
        print(f"  req {uid}: new={len(toks)}  ttft={ttft_ms:.2f}ms  "
              f"itl_mean={float(itl.mean()):.2f}ms  "
              f"tokens={toks[:8]}{'...' if len(toks) > 8 else ''}")
    wall = time.time() - t0
    server.shutdown()
    print(json.dumps({
        "mode": "stream", "overlap": eng.overlap,
        "wall_s": round(wall, 3), "tokens_generated": total,
        "tok_per_s": round(total / wall, 2),
    }))


def _serve_batch(eng: ServingEngine, cfg, args) -> None:
    """Default mode: submit everything, run to completion, print the
    per-request table and throughput summary."""
    t0 = time.time()
    for r in _workload(cfg, args):
        eng.submit(Request(**r))
    results = eng.run()
    wall = time.time() - t0

    total_new = sum(len(r.output) for r in results.values())
    for uid in sorted(results):
        r = results[uid]
        print(f"  req {uid}: prompt={r.prompt_len}  new={len(r.output)}  "
              f"prefill={r.prefill_s * 1e3:.1f}ms  "
              f"decode={r.decode_s * 1e3:.1f}ms  "
              f"tokens={r.output[:8]}{'...' if len(r.output) > 8 else ''}")
    print(json.dumps({
        "wall_s": round(wall, 3),
        "tokens_generated": total_new,
        "tok_per_s": round(total_new / wall, 2),
        "arena_persistent_bytes": eng.arena.usage().persistent,
    }))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-32b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="per-token streaming through StreamingServer "
                         "(overlapped decode where supported)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    eng = _build_engine(args)
    print(f"arch={cfg.arch_id}  requests={args.requests}  "
          f"slots={args.slots}  mode={'stream' if args.stream else 'batch'}")
    if args.stream:
        _serve_stream(eng, cfg, args)
    else:
        _serve_batch(eng, cfg, args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
