"""Batched serving engine — the pod-scale analogue of the TF Micro
invoke loop (paper §4.1), with the same allocation discipline:

  * ALL buffers (decode slots, KV cache, sampling state) are created at
    engine construction — nothing allocates inside the serving loop
    (the paper's "no allocation after init" invariant, C3);
  * cache capacity is budgeted through the SAME TwoStackArena +
    memory-planner machinery the micro interpreter uses: KV is a
    persistent (interpreter-lifetime) allocation, prefill scratch is a
    function-lifetime head allocation released between requests;
  * continuous batching: fixed decode slots, requests admitted as slots
    free up, one fused decode step advances every active slot;
  * the compiled prefill/decode steps resolve through the op registry
    tag chain (``("pallas", "reference")`` by default, §4.7–4.8) —
    vendor-optimized serving kernels shadow the reference ones per-op
    with no engine changes, exactly like the micro interpreter's
    ``TAGS=`` build mechanism.

Compile-once invariants (what callers may rely on):

  * **traced once** — the decode step is jitted at engine construction
    with the resolved registration's eval, context, and OpDef bound;
    the prefill step is jitted once per prompt-length *bucket* when
    bucketing is active (the default for dense/vlm/moe) and once per
    distinct prompt length otherwise.  Model family, cache layout,
    slot count, and window are baked in then.
  * **donated** — the KV state (the contiguous ring or the paged
    pool), and nothing else, into the decode program
    (``DecodeStep.consume``): the step updates it in place and the
    engine keeps only the KV it hands back, so a step holds one copy
    of the cache, not its input and its output.  A plain call of the
    step (``engine._decode(inputs)``) donates a copy instead, so a
    replay or a wrapper keeps what it passed.  Sampling state and
    weights are never donated; the ARENA accounts capacity (KV is an
    interpreter-lifetime tail allocation) but does not back device
    buffers here.
  * **may vary per call** — token values, per-slot lengths, and which
    slots are live.  Admitting a request writes ONLY slot bookkeeping
    and cache rows; it never retraces, which is what keeps continuous
    batching allocation-free inside the loop.

Four host-side degrees of freedom ride on top (docs/SCHEDULING.md,
docs/PREEMPTION.md):

  * **admission order is policy-driven** — a ``SchedulingPolicy``
    (FIFO / priority-with-aging / EDF over ``Request.deadline_us`` /
    per-tenant WFQ) picks which queued request takes a free slot.
    Policies reorder the Python queue only; masks, shapes, and
    programs are untouched, so changing policy never recompiles.
  * **bucketed prefill** — prompt lengths are quantized to power-of-two
    buckets (``BucketTable``): the prompt is right-padded to its bucket
    and the prefill step compiles once per *bucket*, not per *length*.
    Safe for families whose decode masks the KV cache by per-slot
    length AND whose prefill math is per-position (dense/vlm): padded
    rows are positionally masked to -1e30 before softmax, so decoded
    tokens are bit-identical to the exact-length path (asserted in
    tests/test_scheduling.py).  MoE buckets too: its serving dispatch
    is dropless (every expert's capacity is every token), and a traced
    ``n_valid`` scalar keeps the padded positions out of routing, so
    the bucket routes exactly what the true length routes
    (``lm.moe_dispatch``) — one compile per bucket.  SSM and
    hybrid keep exact-length (or CHUNKED, below) prefill: their
    recurrent state integrates every input position, masked or not,
    so forcing a bucket table onto them raises
    ``UnsupportedFamilyError``.
  * **chunked prefill** (``prefill_chunk=``) — a long prompt advances
    ONE fixed-size chunk per engine step instead of running its whole
    prefill inside the admission path, so prefill no longer
    monopolizes the engine between decode steps.  Dense/vlm chunk
    through ``SERVING_PREFILL_CHUNK`` (start offset a traced scalar →
    one compiled chunk program total); ssm/hybrid chunk through
    ``SERVING_PREFILL_CHUNK_STATE``, which carries the recurrent
    (conv, SSD) state — plus hybrid's shared-attn KV — as a traced
    argument: a chunk boundary is just a state checkpoint, and the
    padded tail of the final chunk is an exact state no-op
    (dt masked to zero).  MoE cannot chunk (expert capacity depends
    on the token count integrated so far) and raises the typed error.
  * **preemption** (``preempt=``) — when every slot is busy and the
    queue holds a tighter request, a ``PreemptionPolicy`` picks a
    running victim; its continuation state (KV rows + slot
    bookkeeping, or its half-filled chunked-prefill cache) is
    checkpointed HOST-SIDE into a ``SlotCheckpoint``, the request is
    re-queued, and the urgent one takes the slot.  Restoring later is
    bit-identical (decode is a pure function of the restored state)
    and, like every scheduling decision, touches no traced value — so
    preempt/resume cycles never recompile.

A fifth degree of freedom changes the KV layout itself
(docs/ARCHITECTURE.md §8):

  * **paged KV** (``kv_block=``) — instead of one contiguous
    ``cache_len`` ring per slot, KV lives in fixed-size blocks inside
    a shared physical pool sized independently of ``max_slots``
    (``kv_pool_blocks=``), and each slot holds a row of a traced
    ``(max_slots, cache_len // kv_block)`` block table.  Slots map
    blocks ON DEMAND as they decode (a two-phase reserve/map contract
    on ``PagedKVPool`` makes mid-decode growth infallible), so
    admission is bounded by blocks actually in use, not worst-case
    slot length — more concurrent sequences at the same HBM budget.
    Checkpoint/restore becomes a block-table handoff: evicting a slot
    moves its block IDS into the checkpoint and zeroes its table row;
    restoring writes them into the new slot's row — no KV rows are
    copied either way, and since the table is a traced argument,
    admit/retire/grow/restore never recompile.  Gated to families
    with the dense (KH, C, dh) ring layout (dense/moe/vlm).

A sixth overlaps the host with the device (docs/STREAMING.md):

  * **overlapped decode** (``overlap=True``) — readback is deferred
    ONE step: the engine dispatches decode step i+1 (its input token
    a device future from a tiny jitted argmax) before blocking on
    step i's tokens, so sampling/bookkeeping for step i runs while
    step i+1 computes on device.  The decode program itself is the
    same single traced table entry sync mode runs, and the tokens are
    bit-identical (asserted per family by the conformance matrix's
    ``streaming`` column).  Per-token delivery rides on ``on_token``:
    a ``StreamEvent`` per emitted token, in order and exactly once —
    across preemption/restore too, because every snapshot path drains
    the in-flight step first.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arena import TwoStackArena, align_up
from repro.core.executor import (BucketTable, InflightStep, PagedKVPool,
                                 pin_tree)
from repro.core.op_resolver import MicroMutableOpResolver
from repro.core.schema import OpCode, OpDef
from repro.kernels import ops as _vendor_kernels  # registers tag="pallas"
from repro.models.common import ModelConfig
from repro.models.registry import ModelBundle

from . import ops as serving_ops  # registers tag="reference" serving ops
from .errors import UnsupportedFamilyError
from .scheduling import (PreemptionPolicy, SchedulingPolicy,
                         get_policy, get_preemption)

DEFAULT_TAGS = ("pallas", "reference")

# families each fast path supports — the per-family safety arguments
# live in docs/SCHEDULING.md §2 and docs/PREEMPTION.md §4.
#
# BUCKETED: decode masks the KV cache by per-slot length, so
# right-padded (bucketed) prefill is bit-identical to exact-length
# prefill.  "moe" qualifies via its masked dropless dispatch
# (lm.moe_dispatch: every expert queues the whole bucket, and only the
# true length's tokens route).  NOT "ssm"/"hybrid": recurrent state
# integrates every position, masked or not.
BUCKETED_FAMILIES = ("dense", "vlm", "moe")
# CHUNKED: dense/vlm via the KV-offset chunk op; ssm/hybrid via the
# recurrent-state chunk op (carried state is a traced argument).  NOT
# "moe": expert capacity depends on the token count integrated so
# far, so per-chunk dispatch diverges from the one-shot run.
CHUNKED_FAMILIES = ("dense", "vlm", "ssm", "hybrid")
# chunk through SERVING_PREFILL_CHUNK_STATE (carried recurrent state)
RECURRENT_FAMILIES = ("ssm", "hybrid")
# PAGED: needs the dense (KH, C, dh) ring layout
PAGED_FAMILIES = ("dense", "moe", "vlm")
# SHARDED: families whose param/cache trees the sharding policy
# (distributed/sharding.py) partitions over a serving mesh's ``model``
# axis — heads/FFN/experts for attention families, the SSD head dim
# for recurrent ones.  NOT "audio": the encoder-decoder serving path
# (cross-KV staging at admission) has not been partition-qualified.
SHARDED_FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm")
# STREAMING: families qualified for the overlapped (async) decode loop
# (``overlap=True``): readback deferred one step, greedy sampling
# moved onto the device so the next step's tokens are a device future.
# NOT "audio": the encoder-decoder serving path (cross-KV staged at
# admission) has not been qualified for deferred readback.
STREAMING_FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm")


def default_clock() -> int:
    """Host time in µs — the clock policies age/deadline against.
    Engines and hosts accept a ``clock`` override so the arrival
    benchmark can drive the same scheduling code on virtual time.
    It is ``time.monotonic()``'s clock in whole µs, so a stamp taken
    here and a ``time.monotonic()`` reading subtract directly."""
    return time.monotonic_ns() // 1000


@dataclasses.dataclass
class Request:
    """One pod-scale generation request: a prompt plus decode budget,
    and the scheduling fields admission policies key on (``priority``:
    lower admits first; ``deadline_us``: absolute host µs for EDF;
    ``arrival_us``: stamped at submit() when not provided)."""

    uid: int
    tokens: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0            # 0 = greedy
    extras: Optional[Dict[str, np.ndarray]] = None   # vision / frames
    priority: int = 0                   # lower = more urgent
    deadline_us: Optional[int] = None   # absolute host time, EDF key
    arrival_us: Optional[int] = None    # stamped at submit()
    tenant: str = ""                    # WFQ quota label


@dataclasses.dataclass
class RequestResult:
    """Accumulated outcome of a Request: emitted tokens and timings.
    ``preemptions`` counts how many times the request was evicted from
    a slot and later resumed (0 = ran uninterrupted).

    Engine-clock stamps: ``arrival_us`` is the request's (copied from
    ``Request.arrival_us``), ``admitted_us`` is when it first took a
    slot (a restore after preemption keeps the first), and
    ``first_token_us`` is when its first token was emitted.  So
    ``admitted_us - arrival_us`` is its queue wait,
    ``first_token_us - admitted_us`` its service time to the first
    token, and ``first_token_us - arrival_us`` its TTFT.

    ``prefill_s`` and ``decode_s`` are host seconds: dispatching the
    prefill and decode calls, plus, under ``overlap``, the wait on each
    step's tokens.  JAX dispatches asynchronously, so they are host
    dispatch and wait time, not device time."""

    uid: int
    prompt_len: int
    output: List[int] = dataclasses.field(default_factory=list)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    done: bool = False
    preemptions: int = 0
    first_token_us: Optional[int] = None
    arrival_us: Optional[int] = None
    admitted_us: Optional[int] = None


@dataclasses.dataclass
class StreamEvent:
    """One streamed token, delivered through the engine's ``on_token``
    callback the moment the host learns it (docs/STREAMING.md).

    The ordering contract callers may rely on: per ``uid``, events
    arrive with ``index`` counting 0, 1, 2, … with no gaps and no
    repeats — across preemption/restore and replica routing included —
    and ``token == results[uid].output[index]`` always.  ``final`` is
    True on exactly the request's last event.  ``t_us`` is the engine
    clock at emission (virtual µs under a virtual clock, host µs
    otherwise), so TTFT/ITL fall straight out of the event stream."""

    uid: int
    index: int      # position in the request's output (0-based)
    token: int
    t_us: int       # engine clock at emission
    final: bool     # True on the request's last token


@dataclasses.dataclass
class SlotCheckpoint:
    """A preempted pod request's continuation state, host-side
    (docs/PREEMPTION.md) — the engine analogue of the ragged pool's
    ``LaneCheckpoint``.

    ``phase`` records where the request was interrupted: ``"decode"``
    checkpoints the slot's KV rows plus the (length, next token,
    remaining budget) triple the jitted decode step is a pure function
    of — restoring them replays the run bit-identically; ``"prefill"``
    checkpoints a chunked prefill in flight (its batch=1 cache and how
    many prompt tokens it has integrated).  Values are np copies: a
    checkpoint pins host memory only, never a device buffer, and
    nothing traced is captured — restore can never recompile.

    On a PAGED engine (``kv_block=``) the checkpoint carries no KV at
    all: ``cache`` is None and ``blocks`` pins the slot's physical
    block ids (plus its unspent worst-case ``reserved`` count) — the
    KV rows stay in the shared pool untouched, and restore just writes
    the ids into the new slot's block-table row (a value update of a
    traced argument: no copy, no retrace)."""

    phase: str                          # "decode" | "prefill"
    cache: Any                          # batch=1 cache pytree (np leaves)
    length: int = 0                     # absolute position (decode)
    cur_token: int = 0                  # next token to feed (decode)
    budget: int = 0                     # remaining new tokens (decode)
    done_tokens: int = 0                # prompt tokens integrated (prefill)
    blocks: Optional[List[int]] = None  # paged: pinned physical block ids
    reserved: int = 0                   # paged: unspent reservation


@dataclasses.dataclass
class _ChunkState:
    """A slot mid-chunked-prefill: the request, its private batch=1
    cache, and how many prompt tokens have been integrated so far."""

    req: Request
    cache1: Any
    done: int


def _cache_bytes(tree: Any) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def _split_step(step, kv_sharding, params, kv, rest):
    logits, kv = step((params, kv, *rest))
    if kv_sharding is not None:
        kv = jax.lax.with_sharding_constraint(kv, kv_sharding)
    return logits, kv


class DecodeStep:
    """The engine's decode program: ``step`` — a decode registration's
    ``eval`` with its context and op bound — called with one tuple
    ``(params, kv, *rest)``, jitted once with only ``kv`` (the ring or
    the paged pool) donated.  On a mesh, ``kv_sharding`` (the engine's
    KV sharding) holds the KV the step hands back to the placement it
    came in on, so the donated buffers are reused: left to itself,
    GSPMD may return another placement (a replicated paged pool, with
    fewer KV heads than devices), which the engine's re-pin would then
    copy every step.

    ``consume(inputs)`` gives the program the caller's KV, which it
    updates in place: the caller must drop it and keep the KV it gets
    back.  A plain call, ``step(inputs)``, donates a copy, so what it
    was handed stays valid (a replay, a recorder, a test's wrapper);
    it costs one pass over the whole KV.  Both run the same compiled
    program (``jit_cache_size`` reads it)."""

    def __init__(self, step, kv_sharding=None):
        self._jit = jax.jit(functools.partial(_split_step, step,
                                              kv_sharding),
                            donate_argnums=1)

    def consume(self, inputs):
        """Run on ``inputs``, donating its KV; returns (logits, kv)."""
        params, kv, *rest = inputs
        return self._jit(params, kv, tuple(rest))

    def __call__(self, inputs):
        """Run on a copy of ``inputs``' KV, which stays valid: one more
        pass over the whole KV than ``consume``."""
        params, kv, *rest = inputs
        return self.consume((params, jax.tree.map(jnp.copy, kv), *rest))

    def lower(self, inputs):
        """Lower the program for ``inputs`` (arrays or shapes)."""
        params, kv, *rest = inputs
        return self._jit.lower(params, kv, tuple(rest))

    def _cache_size(self) -> int:
        return self._jit._cache_size()


class ServingEngine:
    """One model, ``max_slots`` concurrent sequences."""

    def __init__(self, bundle: ModelBundle, params: Any, *,
                 max_slots: int = 4, cache_len: int = 256,
                 arena: Optional[TwoStackArena] = None,
                 arena_bytes: Optional[int] = None, seed: int = 0,
                 tags: Sequence[str] = DEFAULT_TAGS,
                 policy: Any = None, clock=None,
                 prefill_buckets: Any = None,
                 prefill_chunk: Any = None, preempt: Any = None,
                 kv_block: Any = None,
                 kv_pool_blocks: Optional[int] = None,
                 weight_dtype: Any = None, kv_dtype: Any = None,
                 mesh: Any = None,
                 overlap: bool = False, on_token: Any = None):
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.params = params
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.policy: SchedulingPolicy = get_policy(policy)
        self.preempt: Optional[PreemptionPolicy] = get_preemption(preempt)
        self.clock = clock if clock is not None else default_clock
        # overlap: defer readback one step — dispatch decode i+1 before
        # blocking on decode i's tokens (docs/STREAMING.md).  Greedy
        # sampling moves onto the device (a tiny separate jitted argmax,
        # bit-identical to the host path) so the next step's cur_tokens
        # is a device future and the host never sits in the dispatch
        # chain.  on_token: per-token StreamEvent callback, fired in
        # order and exactly once in BOTH modes (sync engines stream too;
        # overlap just delivers each token one step later while the
        # device keeps busy).
        self.overlap = bool(overlap)
        if self.overlap and self.cfg.family not in STREAMING_FAMILIES:
            raise UnsupportedFamilyError(
                self.cfg.family, "overlapped (async) decode",
                supported=STREAMING_FAMILIES)
        self.on_token = on_token
        self._inflight: Optional[InflightStep] = None
        # prefill_buckets: None/True = auto (on for length-masked-
        # decode families, when the cache can hold at least the
        # smallest bucket), False = off, or a (shared) BucketTable
        self.bucket_table: Optional[BucketTable] = None
        if prefill_buckets is None or prefill_buckets is True:
            if self.cfg.family in BUCKETED_FAMILIES and cache_len >= 8:
                self.bucket_table = BucketTable(min_bucket=8,
                                                max_bucket=cache_len)
        elif prefill_buckets is not False:
            if not isinstance(prefill_buckets, BucketTable):
                raise TypeError(
                    f"prefill_buckets must be a BucketTable, True, "
                    f"False, or None, got {prefill_buckets!r}")
            if self.cfg.family not in BUCKETED_FAMILIES:
                raise UnsupportedFamilyError(
                    self.cfg.family, "bucketed prefill",
                    supported=BUCKETED_FAMILIES)
            self.bucket_table = prefill_buckets
        # MoE bucketing: every bucketed-moe prefill batch carries the
        # true token count as a traced n_valid scalar (lm.moe_dispatch)
        self._moe_masked = (self.cfg.family == "moe"
                            and self.bucket_table is not None)
        # prefill_chunk: None/False/0 = off, True = auto size (the
        # bucket table's min bucket, 8 when bucketing is off), int =
        # that many tokens per chunk.  dense/vlm chunk at a traced KV
        # offset; ssm/hybrid chunk through the recurrent-state op;
        # moe cannot chunk (capacity depends on tokens integrated).
        self.chunk_tokens = 0
        self._recurrent_chunk = False
        if prefill_chunk:
            if self.cfg.family not in CHUNKED_FAMILIES:
                raise UnsupportedFamilyError(
                    self.cfg.family, "chunked prefill",
                    supported=CHUNKED_FAMILIES)
            self._recurrent_chunk = self.cfg.family in RECURRENT_FAMILIES
            if prefill_chunk is True:
                self.chunk_tokens = (self.bucket_table.min_bucket
                                     if self.bucket_table else 8)
            else:
                if int(prefill_chunk) < 1:
                    raise ValueError(
                        f"prefill_chunk must be >= 1, got {prefill_chunk}")
                self.chunk_tokens = int(prefill_chunk)
        # weight_dtype / kv_dtype: quantized serving (docs/
        # QUANTIZATION.md).  "int8"/"int4" weights quantize ONCE here —
        # the resident tree stays quantized for the engine's lifetime
        # and the SERVING_*_Q ops dequantize per layer INSIDE the traced
        # step; kv_dtype="int8" builds the int8 + per-head-scale cache
        # layout (ring or paged pool alike).  Composes with bucketed
        # prefill and paging; NOT with chunked prefill (the chunk ops
        # write fp KV rows) or mesh sharding (quantized marker dicts
        # are not partition-qualified) — typed refusals at init, like
        # every other family gate.
        self.weight_dtype = weight_dtype
        self.kv_dtype = kv_dtype
        self.quantized = bool(weight_dtype or kv_dtype)
        if self.quantized:
            from repro.models.lm_quant import (KV_DTYPES, WEIGHT_DTYPES,
                                               quantize_lm_params)
            if weight_dtype is not None \
                    and weight_dtype not in WEIGHT_DTYPES:
                raise ValueError(
                    f"weight_dtype must be one of {WEIGHT_DTYPES} or "
                    f"None, got {weight_dtype!r}")
            if kv_dtype is not None and kv_dtype not in KV_DTYPES:
                raise ValueError(
                    f"kv_dtype must be one of {KV_DTYPES} or None, "
                    f"got {kv_dtype!r}")
            if self.cfg.family not in serving_ops.WEIGHT_QUANT_FAMILIES:
                raise UnsupportedFamilyError(
                    self.cfg.family, "quantized serving (SERVING_*_Q)",
                    supported=serving_ops.WEIGHT_QUANT_FAMILIES)
            if kv_dtype and self.cfg.family not in \
                    serving_ops.KV_QUANT_FAMILIES:
                raise UnsupportedFamilyError(
                    self.cfg.family,
                    "int8 KV cache (requires a dense (KH, C, dh) "
                    "cache layout)",
                    supported=serving_ops.KV_QUANT_FAMILIES)
            if self.chunk_tokens:
                raise ValueError(
                    "prefill_chunk does not compose with quantized "
                    "serving (the chunk ops write fp KV rows)")
            if mesh is not None:
                raise ValueError(
                    "mesh does not compose with quantized serving "
                    "(quantized marker dicts are not "
                    "partition-qualified)")
            if weight_dtype:
                self.params = params = quantize_lm_params(
                    params, self.cfg, weight_dtype)
        # resident weight bytes — with kv_bytes below, the benchmark's
        # HBM-footprint hook (quantized engines report the QUANTIZED
        # tree: int8/int4 payloads + f32 scales)
        self.param_bytes = _cache_bytes(self.params)
        dtype = self.cfg.jnp_dtype()
        # kv_block: None/0 = contiguous per-slot rings (the default);
        # int = paged mode with that block size.  kv_pool_blocks sizes
        # the shared physical pool (default: enough for every slot at
        # full length + the garbage block — same bytes as contiguous;
        # the occupancy win comes from passing LESS than that).
        self.kv_block = int(kv_block) if kv_block else 0
        self.paged = bool(self.kv_block)
        if self.paged:
            if self.cfg.family not in PAGED_FAMILIES:
                raise UnsupportedFamilyError(
                    self.cfg.family,
                    "paged KV (requires a dense (KH, C, dh) cache "
                    "layout)", supported=PAGED_FAMILIES)
            if cache_len % self.kv_block:
                raise ValueError(
                    f"kv_block must divide cache_len, got "
                    f"{self.kv_block} vs {cache_len}")
            self.n_table = cache_len // self.kv_block

        # --- arena accounting (C3/C4): KV is interpreter-lifetime ----
        # (the KV itself is made below, once its placement is known)
        if self.paged:
            n_blocks = (int(kv_pool_blocks) if kv_pool_blocks
                        else max_slots * self.n_table + 1)
            kv_rows = (n_blocks, self.kv_block)
            self.pool = PagedKVPool(n_blocks, self.kv_block)
            self.block_tables = jnp.zeros((max_slots, self.n_table),
                                          jnp.int32)
            self._slot_blocks: List[List[int]] = [
                [] for _ in range(max_slots)]
            self._slot_reserved: List[int] = [0] * max_slots
        else:
            kv_rows = (max_slots, cache_len)
        kv_shape = jax.eval_shape(lambda: self._empty_cache(*kv_rows))
        kv_bytes = _cache_bytes(kv_shape)
        self.kv_bytes = kv_bytes
        if arena is None:
            arena = TwoStackArena(arena_bytes or align_up(
                kv_bytes + (64 << 10)) * 2)
        self.arena = arena
        self.kv_offset = arena.allocate_persistent(kv_bytes, tag="kv_cache")

        # --- mesh sharding (tensor/expert parallel in the engine) -----
        # mesh: None = single-device (the default); a jax Mesh with a
        # ``model`` axis shards the weights and the KV arena (the
        # contiguous rings OR the paged pool) through the repo-wide
        # sharding policy (distributed/sharding.py), while every traced
        # bookkeeping value — block tables, lengths, current tokens —
        # pins fully-replicated.  Values still change every step;
        # PLACEMENTS never do (``pin_tree`` after each eager update),
        # so admit/preempt/restore keep the compile-once contract on a
        # mesh exactly as on one device (docs/ARCHITECTURE.md §9).
        self.mesh = mesh
        self._shard = None
        if mesh is not None:
            if self.cfg.family not in SHARDED_FAMILIES:
                raise UnsupportedFamilyError(
                    self.cfg.family, "mesh-sharded serving",
                    supported=SHARDED_FAMILIES)
            from repro.distributed.sharding import engine_shardings
            c1_shape = jax.eval_shape(
                lambda: bundle.empty_cache(1, cache_len, dtype))
            self._shard = engine_shardings(
                self.cfg, mesh, params, kv_shape,
                global_batch=kv_rows[0], cache1_tree=c1_shape)
            self.params = jax.device_put(params, self._shard["params"])
            if self.paged:
                self.block_tables = self._pin_repl(self.block_tables)
        # the KV is made in its placement: on a mesh, each device makes
        # only its shard (the whole ring of a large model does not fit
        # one device beside that device's share of the weights)
        kv = (jax.jit(lambda: self._empty_cache(*kv_rows),
                      out_shardings=self._shard["cache"])()
              if self._shard else self._empty_cache(*kv_rows))
        if self.paged:
            self.kv_pool = kv
        self.cache = None if self.paged else kv

        # --- slot bookkeeping (host side, fixed size) -----------------
        self.slot_req: List[Optional[RequestResult]] = [None] * max_slots
        self.slot_meta: List[Optional[Request]] = [None] * max_slots
        self.slot_budget = np.zeros(max_slots, np.int64)
        self.lengths = self._pin_repl(jnp.zeros((max_slots,), jnp.int32))
        self.cur_tokens = self._pin_repl(
            jnp.zeros((max_slots, 1), jnp.int32))
        self.active = np.zeros(max_slots, bool)
        # host mirror of `lengths` — the overlap loop grows paged block
        # tables at DISPATCH time (the device value is still a future
        # then), and the sync loop keeps it in step for free
        self._len_host = np.zeros(max_slots, np.int64)
        self.rng = np.random.default_rng(seed)
        self.queue: List[Request] = []
        self.results: Dict[int, RequestResult] = {}
        # preemption / chunked-prefill state (host side)
        self._chunking: Dict[int, _ChunkState] = {}
        self._ckpt: Dict[int, SlotCheckpoint] = {}
        # what the last step() did — the benchmark's virtual-clock cost
        # hook: prefill token counts, chunk dispatches, decode dispatch;
        # where its wall time went: ``readback_s`` blocked on the
        # device's tokens, ``host_s`` the rest of the tick; and, for
        # moe, the expert-FFN rows its programs computed
        # (``expert_rows``) against those routing asked for
        # (``routed_rows``), both summed over the MoE layers
        self._moe_layers = (
            self.cfg.n_layers - (1 if self.cfg.first_layer_dense_ff else 0)
            if self.cfg.n_experts else 0)
        self.last_step: Dict[str, Any] = self._fresh_step()

        # --- compiled steps (init-time, like interpreter prepare) -----
        # Resolve prefill/decode through the op registry tag chain: the
        # serving analogue of MicroMutableOpResolver.add() at model load.
        # prepare() runs once here (it may bake family decisions into
        # op_data); eval is jitted with context and op bound, so the
        # traced step is a pure function of (params, cache, tokens, ...).
        prefill_code = OpCode.SERVING_PREFILL
        decode_code = (OpCode.SERVING_DECODE_PAGED if self.paged
                       else OpCode.SERVING_DECODE)
        qparams: Dict[str, Any] = {}
        if self.quantized:
            # two opcodes cover the whole quantized matrix: paged-ness,
            # KV quant, and the weight dtype ride OpDef.params (baked
            # into op_data at prepare) — still one compiled program per
            # engine, and per-opcode tag fallback works unchanged
            prefill_code = OpCode.SERVING_PREFILL_Q
            decode_code = OpCode.SERVING_DECODE_Q
            qparams = {"paged": self.paged, "kv_q": bool(kv_dtype),
                       "weight_dtype": weight_dtype}
        if self.paged:
            chunk_code = OpCode.SERVING_PREFILL_CHUNK_PAGED
        elif self._recurrent_chunk:
            chunk_code = OpCode.SERVING_PREFILL_CHUNK_STATE
        else:
            chunk_code = OpCode.SERVING_PREFILL_CHUNK
        opcodes = [prefill_code, decode_code]
        if self.chunk_tokens:
            opcodes.append(chunk_code)
        self.resolver = MicroMutableOpResolver(tags).add_many(opcodes)
        window = self.cfg.sliding_window
        self._prefill_op = OpDef(prefill_code, (), (),
                                 params={"cache_len": cache_len,
                                         "window": window, **qparams})
        self._decode_op = OpDef(decode_code, (), (),
                                params={"window": window, **qparams})
        prefill_reg = self.resolver.resolve(prefill_code)
        decode_reg = self.resolver.resolve(decode_code)
        pctx = serving_ops.ServingContext(bundle, mesh=mesh)
        prefill_ctx = serving_ops.ServingContext(
            bundle, prefill_reg.prepare(pctx, self._prefill_op).op_data)
        decode_ctx = serving_ops.ServingContext(
            bundle, decode_reg.prepare(pctx, self._decode_op).op_data)
        self._decode = DecodeStep(
            functools.partial(decode_reg.eval, decode_ctx, self._decode_op),
            self._shard["cache"] if self._shard else None)
        # overlap mode's device-side greedy sampler: its OWN tiny jitted
        # program (the decode program stays byte-for-byte the one sync
        # mode runs, so jit_cache_size(self._decode) == 1 holds either
        # way), replicating the host `_sample(logits, 0.0)` math —
        # slice to the true vocab, cast to f32, argmax with first-max
        # tie-break — so streamed tokens are bit-identical to sync.
        vocab = self.cfg.vocab
        self._argmax = jax.jit(
            lambda lg: jnp.argmax(lg[:, :vocab].astype(jnp.float32),
                                  axis=-1).astype(jnp.int32))
        # prefill jits once per prompt-length BUCKET when bucket_table
        # is set (BUCKETED_FAMILIES only: decode masks KV by length,
        # so padding is invisible, and moe additionally carries the
        # traced n_valid scalar); exact-length
        # otherwise — see the BUCKETED_FAMILIES comment for why
        # ssm/hybrid are out
        self._prefill = jax.jit(functools.partial(
            prefill_reg.eval, prefill_ctx, self._prefill_op))
        # the chunk step: fixed (1, chunk_tokens) token shape, start
        # offset a TRACED scalar — one compiled program serves every
        # chunk of every prompt (prepare() re-checks the family gate)
        self._prefill_chunk = None
        if self.chunk_tokens:
            chunk_op = OpDef(chunk_code, (), (),
                             params={"window": window})
            chunk_reg = self.resolver.resolve(chunk_code)
            chunk_ctx = serving_ops.ServingContext(
                bundle, chunk_reg.prepare(pctx, chunk_op).op_data)
            self._prefill_chunk = jax.jit(functools.partial(
                chunk_reg.eval, chunk_ctx, chunk_op))

    @classmethod
    def from_profile(cls, bundle: ModelBundle, params: Any,
                     profile: Any = None, **kw) -> "ServingEngine":
        """Construct an engine from a ``CalibrationProfile``
        (``repro.core.costmodel``) instead of hand-picked constants:
        the profile's solved bucket levels become the engine's
        ``BucketTable`` and its solved ``prefill_chunk`` the chunk
        size, with no re-measurement.  ``cache_len`` defaults to the
        capacity the profile was calibrated at.

        The profile must match this model + cache capacity
        (``profile.matches``) AND the running backend
        (``profile.matches_backend``) — a profile measured on another
        model or another piece of hardware is someone else's cost
        landscape and is refused loudly.  Explicit keyword overrides
        win over the profile (pass
        ``prefill_buckets=``/``prefill_chunk=`` to pin them), and a
        missing profile is simply the ordinary constructor: the
        no-profile fallback is today's defaults.

        With ``profile=None`` the profile CACHE is consulted: a
        profile previously saved under
        ``benchmarks/results/profiles/`` for this model + cache_len
        (``save_cached_profile``) is loaded and applied; no cached
        profile — or one measured on another backend — quietly falls
        back to the ordinary constructor (a cache miss is not an
        error, unlike an explicitly passed stale profile)."""
        if profile is None:
            from repro.core.costmodel import (load_cached_profile,
                                              profile_model_key)
            key = profile_model_key(bundle.cfg, kw.get("cache_len", 256))
            profile = load_cached_profile(key)
            if profile is not None and not profile.matches_backend():
                profile = None
            if profile is None:
                return cls(bundle, params, **kw)
        kw.setdefault("cache_len", profile.cache_len)
        if not profile.matches(bundle.cfg, kw["cache_len"]):
            from repro.core.costmodel import profile_model_key
            raise ValueError(
                f"profile was calibrated for {profile.model_key!r}, "
                f"not {profile_model_key(bundle.cfg, kw['cache_len'])!r}"
                f" — re-calibrate (or share deliberately through "
                f"MultiTenantHost(profile=...))")
        if not profile.matches_backend():
            import jax
            raise ValueError(
                f"profile was measured on backend "
                f"{profile.meta.get('backend')!r}, but this process "
                f"runs on {jax.default_backend()!r} — costs are "
                f"hardware facts; re-calibrate on this backend")
        # each solved knob applies only where the family supports the
        # fast path it drives (a profile calibrated on a bucketing
        # family must not force buckets onto an ssm engine)
        if bundle.cfg.family in BUCKETED_FAMILIES:
            kw.setdefault("prefill_buckets", profile.bucket_table())
        if bundle.cfg.family in CHUNKED_FAMILIES:
            kw.setdefault("prefill_chunk", profile.prefill_chunk or None)
        if getattr(profile, "kv_block", 0) \
                and bundle.cfg.family in PAGED_FAMILIES:
            kw.setdefault("kv_block", profile.kv_block)
        return cls(bundle, params, **kw)

    def prefill_compiles(self) -> int:
        """How many distinct prefill programs were traced — the
        trace-count hook.  With bucketing on, this is the number of
        buckets HIT, independent of how many prompt lengths arrived."""
        from repro.core.executor import jit_cache_size
        return jit_cache_size(self._prefill)

    def chunk_compiles(self) -> int:
        """How many distinct chunk-prefill programs were traced — must
        stay 1 however many prompts/chunks ran (the start offset is a
        traced argument, never a shape)."""
        from repro.core.executor import jit_cache_size
        return (jit_cache_size(self._prefill_chunk)
                if self._prefill_chunk is not None else 0)

    # -- mesh placement pins (compile-once on a mesh) -------------------

    def _pin_repl(self, x: Any) -> Any:
        """Pin a traced bookkeeping array (block table, lengths,
        current tokens) fully-replicated on the engine's mesh —
        identity on a single-device engine.  See ``pin_tree``."""
        return pin_tree(x, self._shard["repl"] if self._shard else None)

    def _pin_kv(self, tree: Any) -> Any:
        """Pin the slot KV arena (contiguous cache or paged pool) back
        onto its init-time mesh sharding after an eager host-side
        update, so the jitted decode step sees one placement forever."""
        return pin_tree(tree, self._shard["cache"] if self._shard
                        else None)

    def _pin_c1(self, tree: Any) -> Any:
        """Pin a batch=1 prefill/chunk cache to its mesh sharding so a
        chunk state keeps one placement from first chunk through
        activation (one chunk program total, sharded or not)."""
        return pin_tree(tree, self._shard["cache1"] if self._shard
                        else None)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.arrival_us is None:
            req.arrival_us = self.clock()
        self.queue.append(req)
        self.results[req.uid] = RequestResult(uid=req.uid,
                                              prompt_len=len(req.tokens),
                                              arrival_us=req.arrival_us)

    def insert_slot_state(self, slot: int, new_cache: Any) -> None:
        """Place a prefilled (batch=1) state pytree into slot ``slot``
        — the pod-engine state-INSERTION hook, inverse of
        ``extract_slot_state``.  Pytree-generic: for dense/vlm/moe the
        leaves are KV rings, for ssm/hybrid they are the recurrent
        conv window + SSD state (plus the hybrid shared-attn KV), all
        with batch on axis 1 — so a checkpointed request restores into
        ANY slot, any family, without retracing (the slot index is a
        host-side dynamic_update_slice start, never a shape)."""
        def ins(full, one):
            # batch dim differs per leaf family; find the axis whose size
            # is max_slots and the matching axis of size 1 in `one`
            for ax in range(full.ndim):
                if full.shape[ax] == self.max_slots \
                        and one.shape[ax] == 1:
                    idx = [slice(None)] * full.ndim
                    start = [0] * full.ndim
                    start[ax] = slot
                    return jax.lax.dynamic_update_slice(
                        full, one.astype(full.dtype), tuple(start))
            raise ValueError((full.shape, one.shape))
        self.cache = self._pin_kv(jax.tree.map(ins, self.cache,
                                               new_cache))

    def _padded_prompt(self, tokens: np.ndarray) -> np.ndarray:
        """Right-pad the prefill prompt to its power-of-two bucket so
        the prefill step compiles once per bucket.  Padded positions
        produce KV rows the length-masked decode can never attend to
        (and the first decode steps overwrite them ring-slot by ring
        slot), so the decoded tokens are bit-identical to exact-length
        prefill.  Prompts longer than the largest bucket that fits the
        cache fall back to exact length (the ring-wrap case)."""
        s = len(tokens)
        room = self.cache_len - (self.cfg.n_vision_tokens
                                 if self.cfg.family == "vlm" else 0)
        padded = self.bucket_table.fit(s)
        if padded is None or padded > room:
            return tokens                   # over-cap: exact length
        self.bucket_table.bucket(s)         # committed: count the hit
        if padded == s:
            return tokens                   # already bucket-shaped
        return np.concatenate(
            [tokens, np.zeros(padded - s, tokens.dtype)])

    def _vis(self) -> int:
        """Cache positions the vision prefix occupies (vlm only)."""
        return (self.cfg.n_vision_tokens
                if self.cfg.family == "vlm" else 0)

    def _empty_cache(self, batch: int, length: int) -> Any:
        """A fresh cache/pool tree in the ENGINE'S KV layout: the one
        hook that keeps an int8-KV engine's empty trees in the
        quantized ``{k, v, k_scale, v_scale}`` layout everywhere a fp
        engine would call ``bundle.empty_cache`` (slot arena, paged
        pool, the single-token-prompt prefill cache)."""
        tree = self.bundle.empty_cache(batch, length,
                                       self.cfg.jnp_dtype())
        if self.kv_dtype:
            from repro.models.lm_quant import quantize_cache
            tree = quantize_cache(tree)
        return tree

    # -- paged KV: block accounting (docs/ARCHITECTURE.md §8) -----------

    def _blocks_needed(self, req: Request) -> int:
        """Worst-case blocks for ``req``: prompt + full decode budget,
        capped at the ring capacity.  Reserved (not mapped) at
        admission so on-demand growth can never fail mid-decode."""
        rows = min(self._vis() + len(req.tokens) - 1 + req.max_new_tokens,
                   self.cache_len)
        return max(1, -(-rows // self.kv_block))

    def _paged_admissible(self, req: Request) -> bool:
        """Can ``req`` take a slot right now?  A checkpointed request's
        resources are already pinned in its checkpoint; a fresh one
        needs its worst case reservable from the pool."""
        return (req.uid in self._ckpt
                or self.pool.can_reserve(self._blocks_needed(req)))

    def _ensure_blocks(self, slot: int, upto_pos: int) -> None:
        """Map blocks (debiting the slot's reservation) until the
        slot's table covers cache position ``upto_pos``.  Host-side
        bookkeeping only — ``_sync_table_row`` publishes the row to
        the traced block table (a VALUE update — never retraces)."""
        blocks = self._slot_blocks[slot]
        while (len(blocks) * self.kv_block <= upto_pos
               and len(blocks) < self.n_table):
            phys = self.pool.map_block()
            self._slot_reserved[slot] -= 1
            blocks.append(phys)

    def _table_row(self, slot: int) -> jnp.ndarray:
        """The slot's block table row, from host bookkeeping: mapped
        blocks in logical order, garbage block for the unmapped tail."""
        row = np.zeros(self.n_table, np.int32)
        blocks = self._slot_blocks[slot]
        row[:len(blocks)] = blocks
        return jnp.asarray(row)

    def _sync_table_row(self, slot: int) -> None:
        """Publish the slot's row into the DECODE block table.  Only a
        decoding slot's row may be live there: the fused decode step
        ring-writes EVERY slot row unconditionally, so a slot that is
        inactive or mid-chunked-prefill keeps its decode row pointed at
        the garbage block (its chunk dispatches carry ``_table_row``
        directly) or stale decode writes would corrupt its blocks."""
        self.block_tables = self._pin_repl(
            self.block_tables.at[slot].set(self._table_row(slot)))

    def _scatter_slot_cache(self, slot: int, cache1: Any) -> None:
        """Scatter a contiguous batch=1 cache into the slot's mapped
        blocks (one-shot prefill lands contiguous, then pages in).
        Unmapped table entries point at the garbage block, so the tail
        of the scatter is harmlessly absorbed there."""
        row = self._table_row(slot)
        t, bs = self.n_table, self.kv_block

        def sc(pool, one):
            if pool.ndim == 4:      # per-head KV scales (int8 KV pool)
                l, _, kh, _ = pool.shape
                src = one[:, 0].reshape(l, kh, t, bs).transpose(
                    0, 2, 1, 3)
                return pool.at[:, row].set(jnp.asarray(src, pool.dtype))
            l, _, kh, _, dh = pool.shape
            src = one[:, 0].reshape(l, kh, t, bs, dh).transpose(
                0, 2, 1, 3, 4)
            return pool.at[:, row].set(jnp.asarray(src, pool.dtype))

        self.kv_pool = self._pin_kv(jax.tree.map(sc, self.kv_pool,
                                                 cache1))

    def _release_slot_blocks(self, slot: int) -> None:
        """Return a finished slot's blocks + unspent reservation to the
        pool and point its table row back at the garbage block."""
        self.pool.release(self._slot_blocks[slot],
                          reserved=max(self._slot_reserved[slot], 0))
        self._slot_blocks[slot] = []
        self._slot_reserved[slot] = 0
        self.block_tables = self._pin_repl(
            self.block_tables.at[slot].set(0))

    def _activate_slot(self, req: Request, slot: int,
                       cache1: Any = None, *,
                       length: Optional[int] = None,
                       cur_token: Optional[int] = None,
                       budget: Optional[int] = None) -> None:
        """Hand a prefilled (or restored) request to the decode loop:
        write its cache rows and the slot bookkeeping the jitted decode
        step keys on.  The keyword overrides are the restore path — a
        resumed request continues from its checkpointed (length, next
        token, remaining budget) instead of a fresh prompt."""
        last_pos = (len(req.tokens) - 1 + self._vis()
                    if length is None else length)
        if self.paged:
            # cover everything written so far PLUS the position the
            # next decode step will write (last_pos % capacity), then
            # go live in the decode block table
            self._ensure_blocks(slot, min(last_pos, self.cache_len - 1))
            self._sync_table_row(slot)
        if cache1 is not None:
            if self.paged:
                self._scatter_slot_cache(slot, cache1)
            else:
                self.insert_slot_state(slot, cache1)
        self.slot_req[slot] = self.results[req.uid]
        self.slot_meta[slot] = req
        self.slot_budget[slot] = (req.max_new_tokens if budget is None
                                  else budget)
        self.active[slot] = True
        self._len_host[slot] = last_pos
        self.lengths = self.lengths.at[slot].set(last_pos)
        self.cur_tokens = self.cur_tokens.at[slot, 0].set(
            int(req.tokens[-1]) if cur_token is None else cur_token)

    def _prefill_one(self, req: Request, slot: int) -> None:
        """Prefill tokens[:-1], then hand the LAST prompt token to the
        decode loop: the first decode step integrates it (KV write /
        SSD state update) and emits the first new token — one uniform
        decode path for every family, no double-integration for SSM."""
        t0 = time.perf_counter()
        n = len(req.tokens)
        if n >= 2:
            m = n - 1
            with jax.profiler.TraceAnnotation("engine.prefill",
                                              uid=req.uid, tokens=m):
                prompt = np.asarray(req.tokens[:-1])
                if self.bucket_table is not None:
                    prompt = self._padded_prompt(prompt)
                batch = {"tokens": jnp.asarray(prompt[None])}
                if self._moe_masked:
                    # bucketed MoE: the traced true length keeps the
                    # padded positions out of routing (lm.moe_dispatch).
                    # Emitted even on the over-cap exact-length fallback
                    # (where it masks nothing) so every prefill of a
                    # given shape shares one trace signature.
                    batch["n_valid"] = jnp.int32(m)
                if req.extras:
                    for k, v in req.extras.items():
                        batch[k] = jnp.asarray(v[None])
                _, cache1 = self._prefill((self.params, batch))
            self.last_step["prefill_tokens"].append(len(prompt))
            self._count_moe_rows(len(prompt), m)
            self.policy.charge(req.tenant, 1.0)
        else:   # single-token prompt: slot starts from a fresh cache
            cache1 = self._empty_cache(1, self.cache_len)
        self.results[req.uid].prefill_s += time.perf_counter() - t0
        self._activate_slot(req, slot, cache1)

    # -- chunked prefill (one chunk per engine step) --------------------

    def _chunk_eligible(self, req: Request) -> bool:
        """Chunk when chunking is on, the prompt spans more than one
        chunk, and the padded last chunk still fits the cache without
        ring wrap (past that, fall back to one-shot exact prefill —
        the same over-cap fallback as ``_padded_prompt``)."""
        if not self.chunk_tokens:
            return False
        m = len(req.tokens) - 1
        if m <= self.chunk_tokens:
            return False
        n_chunks = -(-m // self.chunk_tokens)
        return self._vis() + n_chunks * self.chunk_tokens <= self.cache_len

    def _start_chunked(self, req: Request, slot: int) -> None:
        """Admit a long prompt into a slot in PREFILLING state: run the
        FIRST chunk through the ordinary prefill step (fixed
        (1, chunk_tokens) shape — for vlm this is also what integrates
        the vision prefix), park the batch=1 cache in a ``_ChunkState``,
        and let subsequent ``step()`` calls advance one chunk each.

        Recurrent families (ssm/hybrid) skip the one-shot prefill step
        entirely: the carried-state chunk op is seeded with an EMPTY
        cache (zero conv window ≡ the zero left-padding ``_causal_conv``
        assumes, zero SSD state ≡ no history) and EVERY chunk — the
        first included — goes through the single compiled
        SERVING_PREFILL_CHUNK_STATE program, so a chunked ssm/hybrid
        engine traces zero prefill programs."""
        if self._recurrent_chunk:
            cache1 = self._pin_c1(self.bundle.empty_cache(
                1, self.cache_len, self.cfg.jnp_dtype()))
            self._chunking[slot] = _ChunkState(req, cache1, 0)
            self._advance_chunk(slot)
            return
        t0 = time.perf_counter()
        first = np.asarray(req.tokens[:self.chunk_tokens])
        with jax.profiler.TraceAnnotation("engine.prefill", uid=req.uid,
                                          tokens=len(first)):
            batch = {"tokens": jnp.asarray(first[None])}
            if req.extras:
                for k, v in req.extras.items():
                    batch[k] = jnp.asarray(v[None])
            _, cache1 = self._prefill((self.params, batch))
        self.last_step["prefill_tokens"].append(len(first))
        self.policy.charge(req.tenant, 1.0)
        if self.paged:
            # page the first chunk into the pool now; later chunks
            # write the pool directly through the paged chunk op
            self._ensure_blocks(
                slot, min(self._vis() + len(first) - 1,
                          self.cache_len - 1))
            self._scatter_slot_cache(slot, cache1)
            cache1 = None
        else:
            cache1 = self._pin_c1(cache1)
        self._chunking[slot] = _ChunkState(req, cache1, len(first))
        self.results[req.uid].prefill_s += time.perf_counter() - t0

    def _advance_chunk(self, slot: int) -> None:
        """Advance a PREFILLING slot by ONE chunk — one jitted chunk
        dispatch with a traced start offset; the final partial chunk is
        right-padded (its garbage rows sit beyond the prompt length, so
        the length-masked decode can never attend to them and the first
        decode steps overwrite them slot by slot).  When the last
        prompt token's predecessor lands, the slot flips to decoding."""
        cs = self._chunking[slot]
        res = self.results[cs.req.uid]
        t0 = time.perf_counter()
        prompt = np.asarray(cs.req.tokens[:-1])
        tok = prompt[cs.done:cs.done + self.chunk_tokens]
        real = len(tok)
        with jax.profiler.TraceAnnotation("engine.prefill",
                                          uid=cs.req.uid, tokens=real):
            if real < self.chunk_tokens:
                tok = np.concatenate(
                    [tok, np.zeros(self.chunk_tokens - real, tok.dtype)])
            start = cs.done + self._vis()
            if self.paged:
                self._ensure_blocks(
                    slot, min(start + self.chunk_tokens - 1,
                              self.cache_len - 1))
                self.kv_pool = self._pin_kv(self._prefill_chunk(
                    (self.params, self.kv_pool, self._table_row(slot),
                     jnp.asarray(tok[None]), jnp.int32(start))))
            elif self._recurrent_chunk:
                # carried-state dispatch: the chunk's true token count
                # rides along as a traced scalar — the padded tail of
                # the final chunk is an exact state no-op (dt masked to
                # zero), so one compiled program serves full and
                # partial chunks alike
                cs.cache1 = self._pin_c1(self._prefill_chunk(
                    (self.params, cs.cache1, jnp.asarray(tok[None]),
                     jnp.int32(start), jnp.int32(real))))
            else:
                cs.cache1 = self._pin_c1(self._prefill_chunk(
                    (self.params, cs.cache1, jnp.asarray(tok[None]),
                     jnp.int32(start))))
        cs.done += real
        self.last_step["chunks"] += 1
        self.policy.charge(cs.req.tenant, 1.0)
        res.prefill_s += time.perf_counter() - t0
        if cs.done >= len(prompt):
            del self._chunking[slot]
            self._activate_slot(cs.req, slot, cs.cache1)

    # -- preemption: slot checkpoint / evict / restore ------------------

    def extract_slot_state(self, slot: int) -> Any:
        """Slot ``slot``'s model state as a batch=1 pytree of np copies
        — the pod-engine state-EXTRACTION hook ``SlotCheckpoint`` (and
        the host's ``LaneCheckpoint``) carry.  Pytree-generic over the
        family's cache: KV rings for dense/vlm/moe, the recurrent conv
        window + SSD state (f32, exactly as the decode step left them)
        for ssm/hybrid — so restoring via ``insert_slot_state`` resumes
        bit-identically for every family."""
        self.drain()
        def ext(full):
            axes = [ax for ax in range(full.ndim)
                    if full.shape[ax] == self.max_slots]
            if not axes:
                raise ValueError((full.shape, self.max_slots))
            ax = 1 if 1 in axes else axes[0]   # batch is axis 1 for
            idx = [slice(None)] * full.ndim    # every current family
            idx[ax] = slice(slot, slot + 1)
            return np.asarray(full[tuple(idx)])
        return jax.tree.map(ext, self.cache)

    def snapshot_slot(self, slot: int) -> SlotCheckpoint:
        """Capture a running slot's continuation state host-side: the
        chunked-prefill cache + progress for a PREFILLING slot, the KV
        rows + (length, next token, budget) triple for a DECODING one.
        The slot itself is untouched — pair with ``_evict``.  On an
        overlapped engine the in-flight step is drained first, so the
        captured (length, token, budget) triple is always
        post-emission-consistent."""
        self.drain()
        if slot in self._chunking:
            cs = self._chunking[slot]
            if self.paged:
                return SlotCheckpoint(
                    phase="prefill", cache=None, done_tokens=cs.done,
                    blocks=list(self._slot_blocks[slot]),
                    reserved=self._slot_reserved[slot])
            return SlotCheckpoint(
                phase="prefill",
                cache=jax.tree.map(np.asarray, cs.cache1),
                done_tokens=cs.done)
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not running")
        if self.paged:
            # no KV copy: the rows stay in the pool, the checkpoint
            # pins the block ids (checkpoint-as-table-handoff)
            return SlotCheckpoint(
                phase="decode", cache=None,
                length=int(self.lengths[slot]),
                cur_token=int(self.cur_tokens[slot, 0]),
                budget=int(self.slot_budget[slot]),
                blocks=list(self._slot_blocks[slot]),
                reserved=self._slot_reserved[slot])
        return SlotCheckpoint(
            phase="decode", cache=self.extract_slot_state(slot),
            length=int(self.lengths[slot]),
            cur_token=int(self.cur_tokens[slot, 0]),
            budget=int(self.slot_budget[slot]))

    def _evict(self, slot: int) -> Request:
        """Preempt the request running in ``slot``: checkpoint it,
        free the slot, and put the request back on the queue (its
        checkpoint is picked up at re-admission).  Drains any in-flight
        overlapped step first — callers picking a victim must choose
        AFTER the drain (a pending retirement may have freed it)."""
        self.drain()
        if slot in self._chunking:
            req = self._chunking[slot].req
            ckpt = self.snapshot_slot(slot)
            del self._chunking[slot]
        else:
            req = self.slot_meta[slot]
            assert req is not None, f"slot {slot} has no request"
            ckpt = self.snapshot_slot(slot)
            self.active[slot] = False
            self.slot_req[slot] = None
            self.slot_meta[slot] = None
        if self.paged:
            # block ownership moved to the checkpoint: detach the slot
            # (table row back to the garbage block) without releasing
            self._slot_blocks[slot] = []
            self._slot_reserved[slot] = 0
            self.block_tables = self._pin_repl(
                self.block_tables.at[slot].set(0))
        self._ckpt[req.uid] = ckpt
        self.results[req.uid].preemptions += 1
        self.queue.append(req)
        return req

    def _restore_slot(self, req: Request, slot: int,
                      ckpt: SlotCheckpoint) -> None:
        """Re-admit a checkpointed request: a PREFILLING checkpoint
        resumes its chunk loop, a DECODING one re-enters the decode
        loop at exactly the captured state — the jitted decode step is
        a pure function of (cache, token, length), so the continuation
        is bit-identical to the uninterrupted run."""
        if self.paged:
            # block-table handoff: the pinned ids attach to the NEW
            # slot — the KV rows never moved.  A resumed decode goes
            # live in the decode table via _activate_slot's sync; a
            # resumed chunked prefill keeps its decode row on the
            # garbage block (chunk dispatches carry the row directly)
            self._slot_blocks[slot] = list(ckpt.blocks or [])
            self._slot_reserved[slot] = ckpt.reserved
            if ckpt.phase == "prefill":
                self._chunking[slot] = _ChunkState(req, None,
                                                   ckpt.done_tokens)
            else:
                self._activate_slot(req, slot, None, length=ckpt.length,
                                    cur_token=ckpt.cur_token,
                                    budget=ckpt.budget)
            return
        if ckpt.phase == "prefill":
            cache1 = self._pin_c1(jax.tree.map(jnp.asarray, ckpt.cache))
            self._chunking[slot] = _ChunkState(req, cache1,
                                               ckpt.done_tokens)
        else:
            self.insert_slot_state(slot, jax.tree.map(jnp.asarray,
                                                      ckpt.cache))
            self._activate_slot(req, slot, None, length=ckpt.length,
                                cur_token=ckpt.cur_token,
                                budget=ckpt.budget)

    def _admit(self, req: Request, slot: int) -> None:
        """Route an admission: restore a checkpointed request, start a
        chunked prefill for a long prompt, or prefill one-shot.  On a
        paged engine a FRESH admission reserves its worst-case block
        count up front (the caller checked ``_paged_admissible``), so
        every later ``map_block`` is infallible."""
        res = self.results[req.uid]
        if res.admitted_us is None:
            res.admitted_us = self.clock()
        with jax.profiler.TraceAnnotation("engine.admit", uid=req.uid):
            ckpt = self._ckpt.pop(req.uid, None)
            if ckpt is not None:
                self._restore_slot(req, slot, ckpt)
                return
            if self.paged:
                need = self._blocks_needed(req)
                self.pool.reserve(need)
                self._slot_reserved[slot] = need
            if self._chunk_eligible(req):
                self._start_chunked(req, slot)
            else:
                self._prefill_one(req, slot)

    def _sample(self, logits, temperature: float) -> np.ndarray:
        logits = np.asarray(logits[:, :self.cfg.vocab], np.float32)
        if temperature <= 0:
            return logits.argmax(-1)
        z = logits / temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        return np.array([self.rng.choice(len(row), p=row) for row in p])

    # -- streaming + overlapped decode (docs/STREAMING.md) --------------

    def _emit(self, res: RequestResult, tok: int, final: bool) -> None:
        """Append + stream one token — the single place a token becomes
        visible, in both modes, so the output list, the TTFT stamp, and
        the ``on_token`` StreamEvent agree by construction (in order,
        exactly once; preemption/restore cannot double-emit because
        every snapshot path drains first and so captures
        post-emission state)."""
        res.output.append(tok)
        self.last_step["processed"] += 1
        now = self.clock()
        if res.first_token_us is None:
            res.first_token_us = now
        if self.on_token is not None:
            self.on_token(StreamEvent(uid=res.uid,
                                      index=len(res.output) - 1,
                                      token=tok, t_us=now, final=final))

    def drain(self) -> None:
        """Settle the overlapped loop's in-flight decode step, if any:
        block on its tokens and run its host bookkeeping (emission,
        retirement, budget/quota charges).  Public because anything
        doing checkpoint surgery from outside — tests, the router's
        work-stealing, a server shutting down — must see consistent
        slot state first; every internal snapshot/evict path calls it.
        No-op on a sync engine or when nothing is in flight."""
        step, self._inflight = self._inflight, None
        if step is not None:
            self._finish_inflight(step)

    def _finish_inflight(self, step: InflightStep) -> None:
        """Host half of a dispatched decode step: fetch its tokens (the
        deferred ``block_until_ready``) and interpret them against the
        DISPATCH-TIME slot snapshot.  A slot that retired after the
        dispatch (eos/budget is learned one step late) is skipped: its
        extra dispatched decode was wasted device work whose KV writes
        are invisible — overwritten before the slot's next activation,
        or absorbed by the paged garbage block — and whose token is
        dropped here, never emitted."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.readback"):
            toks = step.host_fetch()
        wait = time.perf_counter() - t0
        self.last_step["readback_s"] += wait
        eos = self.cfg.vocab - 1
        with jax.profiler.TraceAnnotation("engine.emit"):
            for slot, res, req in step.slots:
                if res.done or self.slot_req[slot] is not res:
                    continue    # retired between dispatch and readback
                res.decode_s += step.dispatch_s + wait
                self.policy.charge(req.tenant, 1.0)
                tok = int(toks[slot])
                self.slot_budget[slot] -= 1
                done = self.slot_budget[slot] <= 0 or tok == eos
                self._emit(res, tok, final=done)
                if done:
                    res.done = True
                    self.active[slot] = False
                    self.slot_req[slot] = None
                    self.slot_meta[slot] = None
                    if self.paged:
                        self._release_slot_blocks(slot)

    def _dispatch_decode(self) -> Any:
        """Dispatch one fused decode step over every slot, donating the
        cache (ring or paged pool) and keeping the one it returns;
        return its logits (a device future).  A step someone has
        wrapped (a recorder, an injected fault) gets the plain call,
        which leaves what it is handed valid."""
        step = self._decode
        run = step.consume if isinstance(step, DecodeStep) else step
        self._count_moe_rows(self.max_slots, int(self.active.sum()))
        if self.paged:
            logits, kv_pool = run(
                (self.params, self.kv_pool, self.block_tables,
                 self.cur_tokens, self.lengths))
            self.kv_pool = self._pin_kv(kv_pool)
        else:
            logits, cache = run(
                (self.params, self.cache, self.cur_tokens, self.lengths))
            self.cache = self._pin_kv(cache)
        return logits

    def _dispatch_overlapped(self) -> None:
        """Dispatch one fused decode step WITHOUT reading it back, then
        settle the PREVIOUS step while the device works (PR 4's
        double-buffered chunk prefill, generalized to decode).  The
        device-side argmax feeds ``cur_tokens`` as a device future, so
        step i+1's inputs never pass through the host and the only
        blocking transfer — the previous step's tokens — overlaps the
        device executing this one."""
        pend = {s for s, _, _ in self._inflight.slots} \
            if self._inflight is not None else set()
        if self.paged:
            # grow at DISPATCH time from the host length mirror (the
            # device lengths are still a future here): map the block
            # this step's ring write lands in.  Slots whose budget is
            # spent once the in-flight step lands are skipped — their
            # write is absorbed by the garbage block, and mapping it
            # would overdraw the admission-time reservation.
            for slot in range(self.max_slots):
                if not self.active[slot]:
                    continue
                if self.slot_budget[slot] \
                        - (1 if slot in pend else 0) <= 0:
                    continue
                before = len(self._slot_blocks[slot])
                self._ensure_blocks(
                    slot, int(self._len_host[slot]) % self.cache_len)
                if len(self._slot_blocks[slot]) != before:
                    self._sync_table_row(slot)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.decode"):
            logits = self._dispatch_decode()
            toks = self._argmax(logits)
            self.lengths = self.lengths + 1
            self._len_host += 1
            self.cur_tokens = self._pin_repl(toks[:, None])
        self.last_step["decoded"] = True
        prev, self._inflight = self._inflight, InflightStep(
            tokens=toks,
            slots=[(s, self.slot_req[s], self.slot_meta[s])
                   for s in range(self.max_slots) if self.active[s]],
            dispatch_s=time.perf_counter() - t0)
        if prev is not None:
            self._finish_inflight(prev)

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One engine tick: advance chunked prefills by ONE chunk each,
        admit (policy order, displacing a running victim when the
        preemption policy says so), then one fused decode step over the
        active slots.  Returns True if work remains.

        The queue is re-keyed at every free slot, so a deadline that
        became urgent while other requests decoded is picked up here;
        with chunking on, a long prompt's prefill is interleaved
        through these ticks instead of monopolizing the engine.

        ``last_step`` records what the tick did, and splits its wall
        time into ``readback_s`` (blocked on the device's tokens) and
        ``host_s`` (everything else)."""
        t0 = time.perf_counter()
        self.last_step = self._fresh_step()
        more = self._tick()
        self.last_step["host_s"] = (time.perf_counter() - t0
                                    - self.last_step["readback_s"])
        return more

    @staticmethod
    def _fresh_step() -> Dict[str, Any]:
        return {"prefill_tokens": [], "chunks": 0, "decoded": False,
                "processed": 0, "readback_s": 0.0, "host_s": 0.0,
                "expert_rows": 0, "routed_rows": 0}

    def _count_moe_rows(self, tokens: int, live: int) -> None:
        """Add one program's expert-FFN rows to ``last_step``, from the
        host's shapes and slot mask (nothing is read back): its
        dropless dispatch queues all ``tokens`` of its shape at every
        expert, and routing asks for ``live`` x top_k rows, in each MoE
        layer."""
        self.last_step["expert_rows"] += (self._moe_layers * tokens
                                          * self.cfg.n_experts)
        self.last_step["routed_rows"] += (self._moe_layers * live
                                          * self.cfg.top_k)

    def _tick(self) -> bool:
        """The body of ``step``."""
        for slot in list(self._chunking):
            self._advance_chunk(slot)
        if self.queue:
            if self.overlap and self.preempt is not None:
                # settle the in-flight step before any admission or
                # displacement decision: a victim's checkpoint must
                # capture post-emission state, and a retirement still
                # in flight may free the slot the queue needs
                self.drain()
            now = self.clock()
            for slot in range(self.max_slots):
                if self.queue and not self.active[slot] \
                        and slot not in self._chunking:
                    if self.paged:
                        # admission control: the policy's pick only
                        # takes the slot if its worst case fits the
                        # pool's free blocks (restores are pre-pinned)
                        ci = self.policy.select(self.queue, now)
                        if not self._paged_admissible(self.queue[ci]):
                            break
                        self._admit(self.queue.pop(ci), slot)
                    else:
                        self._admit(self.policy.pop(self.queue, now),
                                    slot)
            # displacement: every slot busy, queue still holding work —
            # let the preemption policy evict a running victim for the
            # queue's policy-first candidate (strict-improvement
            # contract bounds this loop by the slot count)
            if self.preempt is not None:
                for _ in range(self.max_slots):
                    if not self.queue:
                        break
                    running = ([(s, self._chunking[s].req)
                                for s in sorted(self._chunking)]
                               + [(s, self.slot_meta[s])
                                  for s in range(self.max_slots)
                                  if self.active[s]])
                    if not running:
                        break
                    ci = self.policy.select(self.queue, now)
                    cand = self.queue[ci]
                    vi = self.preempt.victim([r for _, r in running],
                                             cand, now)
                    if vi is None:
                        break
                    if self.paged and not self._paged_admissible(cand):
                        break   # evicting frees no blocks (they pin
                        # to the checkpoint), so check BEFORE evicting
                    self.queue.pop(ci)
                    slot = running[vi][0]
                    self._evict(slot)
                    self._admit(cand, slot)
        if self.overlap and self.active.any() \
                and self._inflight is not None:
            pend = {s for s, _, _ in self._inflight.slots}
            if all(self.slot_budget[s] - (1 if s in pend else 0) <= 0
                   for s in range(self.max_slots) if self.active[s]):
                # every active slot's budget is spent once the
                # in-flight step lands: drain instead of dispatching
                # a step whose every token would be dropped
                self.drain()
        if not self.active.any():
            self.drain()
            return bool(self.active.any() or self.queue
                        or self._chunking)
        if self.overlap:
            self._dispatch_overlapped()
            return bool(self.active.any() or self.queue
                        or self._chunking or self._inflight is not None)
        with jax.profiler.TraceAnnotation("engine.decode"):
            t0 = time.perf_counter()
            logits = self._dispatch_decode()
            dt = time.perf_counter() - t0
            self.lengths = self.lengths + 1
            self._len_host += 1
        self.last_step["decoded"] = True
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.readback"):
            toks = self._sample(logits, 0.0)
            lens_host = np.asarray(self.lengths)
            new_cur = np.array(self.cur_tokens)    # writable host copy
        self.last_step["readback_s"] += time.perf_counter() - t0
        eos = self.cfg.vocab - 1
        with jax.profiler.TraceAnnotation("engine.emit"):
            for slot in range(self.max_slots):
                if not self.active[slot]:
                    continue
                res = self.slot_req[slot]
                res.decode_s += dt
                self.policy.charge(self.slot_meta[slot].tenant, 1.0)
                tok = int(toks[slot])
                self.slot_budget[slot] -= 1
                new_cur[slot, 0] = tok
                done = self.slot_budget[slot] <= 0 or tok == eos
                self._emit(res, tok, final=done)
                if done:
                    res.done = True
                    self.active[slot] = False
                    self.slot_req[slot] = None
                    self.slot_meta[slot] = None
                    if self.paged:
                        self._release_slot_blocks(slot)
                elif self.paged:
                    # grow on demand: map the block the NEXT decode
                    # step's ring write lands in (covered by the
                    # reservation)
                    before = len(self._slot_blocks[slot])
                    self._ensure_blocks(
                        slot, int(lens_host[slot]) % self.cache_len)
                    if len(self._slot_blocks[slot]) != before:
                        self._sync_table_row(slot)
        with jax.profiler.TraceAnnotation("engine.decode"):
            self.cur_tokens = self._pin_repl(jnp.asarray(new_cur))
        return bool(self.active.any() or self.queue or self._chunking)

    def run(self, max_steps: int = 10_000) -> Dict[int, RequestResult]:
        steps = 0
        while self.step():
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serving loop did not converge")
        return self.results
