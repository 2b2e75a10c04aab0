"""Continuous-batching serve engine on a slot-based KV cache.

Architecture
============
The engine owns ``B = ServeConfig.max_batch`` persistent decode SLOTS over
one preallocated cache (``T.init_cache(cfg, B, max_seq)``).  A slot is a
batch row plus its per-slot serving state; nothing ties slots to a shared
scalar position, so one jitted ``decode_step`` — the same signature every
step, no recompilation — serves all slots at heterogeneous sequence
offsets via per-slot ``int32[B]`` vectors:

  ``pos[b]``    next cache row slot b writes (its RoPE phase is
                ``pos[b] - start[b]``; its attention mask covers cache
                rows ``[start[b], pos[b]]``)
  ``start[b]``  first real row of slot b's prompt (left-pad prefix mask)

Slot lifecycle (the :class:`Scheduler`)
---------------------------------------
``free -> prefilling -> decoding -> free``

* **Admission**: when a slot is free and the request queue is non-empty,
  the next request's prompt is left-padded to a power-of-two bucket ``P``,
  prefilled into a FRESH batch=1 cache in one jitted call, and scattered
  into the freed slot with :func:`repro.models.transformer.write_cache_slot`
  — the other slots' cache rows and recurrent state are untouched and keep
  decoding.  The slot starts with ``start = P - len(prompt)``, ``pos = P``,
  and its first output token sampled from the prefill logits.
* **Decode**: every step runs ONE ``decode_step`` over all B slots at
  their own positions, then ONE vectorized sample (per-slot temperature /
  PRNG key / step counter — no per-slot Python loop, one (B, 2) device->
  host transfer per step carrying each slot's token AND health bit).
* **Eviction**: a slot frees when its request hits its ``eos_id`` or its
  per-request ``max_new`` budget (clamped against ``max_seq``).  Freed
  slots keep decoding garbage (their outputs are ignored and their cache
  rows are fully overwritten by the next admission's scatter), so the
  batch shape — and the jit signature — never changes.  A freed slot is
  parked at ``pos = start = 0`` and does not advance, so the fused flash
  decode scans one KV tile for it.

Determinism / batch invariance
------------------------------
A request's tokens are bit-identical whether it is served solo, in a
static batch, or admitted mid-flight next to longer requests: pad rows are
masked out of attention (and never enter recurrent state), RoPE phases are
relative to ``start``, every per-row reduction sees the same values (exact
zeros elsewhere), and sampling keys derive from the request — not the slot
or the step the batch happens to be at (``fold_in(base_key, request_id)``
then ``fold_in(key, per-request step)``).  Greedy decoding is therefore
exactly invariant; sampled decoding is invariant for a fixed key id —
``serve``/``serve_static`` use the stream index unless ``Request.seed``
pins it, and ``generate`` uses the batch index unless its ``seeds``
argument pins it, so matching ids (e.g. pinned seeds) reproduce the same
sampled stream across all three entry points.

The hybrid family's ring buffer stores a row at physical index ``pos % W``
but ATTENDS the window in age order (oldest -> newest, a relative-offset
gather), so bit-equality holds even after a sequence wraps the window —
the former physical-order caveat is gone.  Attention/SSM families never
had one.

``prefill`` stays ONE jitted call per prompt-length bucket (chunked
whole-prompt attention for the dense family — through the fused posit
flash kernel under ``attn_backend="fused"`` — scanned decode for the other
families; MoE stays scanned so its length-dependent expert capacity keeps
ragged batching exact).  Under ``attn_backend="fused"`` the decode step's
attention ALSO runs the fused Pallas kernel, with per-slot
``q_pos``/``kv_len``/``kv_start`` inputs — per-slot positions end to end.
The decode step is the same jitted ``decode_step`` the multi-pod dry-run
lowers, so what we serve here is what scales there.

Paged KV cache (``ServeConfig.kv_layout="paged"``)
==================================================
The dense slot cache reserves ``max_seq`` rows per slot up front.  The
paged layout replaces each layer's ``(B, max_seq, KV, hd)`` region with a
GLOBAL block pool ``(num_blocks, block_size, KV, hd)`` plus an engine-owned
``int32[B, max_blocks]`` block table per cache side:

  * **Block-table layout** — slot ``b``'s logical cache row ``r`` lives at
    pool row ``(block_tables[b, r // block_size], r % block_size)``.  Block
    ids form one id space across layers (logical block ``j`` uses the same
    pool index in every layer), so tables, refcounts and sharing are
    per-slot, not per-layer.  Block 0 is a reserved write sink for parked
    slots (all-zero table rows); the allocator hands out ids
    ``[1, num_blocks)``.  A slot's table grows one block at a time as its
    ``pos`` crosses block boundaries — per-request reserved HBM scales
    with the tokens actually written, not ``max_seq``.
  * **CoW lifecycle** — every pool block is refcounted.  Admission
    increfs the fully-shared prefix blocks it maps and allocates fresh
    blocks (refcount 1) for the rest.  A PARTIALLY-shared block is never
    mapped: its rows are gathered into the admission's dense mini cache,
    the suffix prefill extends them, and the full copy lands in a freshly
    owned page (copy-on-write as copy-into-allocate — shared storage is
    never mutated, because decode only ever writes a slot's own last
    block, which is by construction unshared).  Eviction decrefs the
    slot's blocks; a registered prefix block whose refcount hits 0 parks
    in an LRU cached list (still matchable) and is reclaimed only when
    the free list runs dry; unregistered blocks return to the free list
    directly.  An admission that cannot get enough blocks is deferred
    until an eviction frees some (or sheds / raises a clean ``ValueError``
    if no request is in flight to ever free one).
  * **Prefix sharing** — admission hashes the prompt's full token blocks
    as a rolling chain and looks the chain up in the allocator's prefix
    table; matches compare the FULL token prefix (hash collisions cannot
    alias) and map the shared pool pages instead of recomputing them —
    prefill runs only from the first unshared token (``t0``).  Sharing is
    an optimization with an invariance CONTRACT: paged admission prefills
    unpadded at start 0, so a prefix block's contents are a pure function
    of the prefix tokens, the kv sequence a sharing request attends is
    value- and order-identical to the one it would have computed, and the
    flash scan's tile geometry is unchanged (virtual ``max_blocks *
    block_size = max_seq``) — decoded tokens are bit-identical dense vs
    paged vs prefix-shared, asserted by ``tests/test_paged_kv.py`` and
    gated by the BENCH_PR6 invariance row.  Sharing is disabled when
    ``numerics.kv_cache_format`` quantizes the cache (prefill attends
    unquantized fresh k/v, so reusing quantized rows would change
    numerics); the paged layout itself still works there.

Families: dense/moe page their kv caches; ssm/hybrid (recurrent O(1)
state) silently keep the dense slot path under ``kv_layout="paged"``.

Packed multi-prompt prefill (``ServeConfig.packed_prefill``)
============================================================
Per-request admission dispatches one batch=1 prefill per queued prompt,
so slots sit idle behind serial prefill latency whenever several free up
at once.  With ``packed_prefill=True`` the admission sweep instead runs
the whole queue head through ONE prefill executable per sweep:

  **pack -> segment prefill -> scatter -> per-slot decode**

  * **Pack** — :meth:`Scheduler.plan_packs` groups the queue head (at
    most one entry per free slot, so nothing is reordered past a request
    that would have been admitted this sweep anyway) into
    ``(bucket_len, num_prompts)`` bins.  Both coordinates are rounded up
    to powers of two — short bins are padded with all-pad DUMMY segments
    — so the executable signature space stays
    ``O(log max_seq * log max_batch)`` and :meth:`ServeEngine.warmup`
    can pre-compile every bin a deployment will ever hit.
  * **Segment prefill** — the dense family concatenates the N prompts
    into ONE ``(1, N * P)`` sequence and runs
    :func:`repro.models.transformer.prefill_packed`: per-token segment
    ids ride the existing ``q_pos``/``kv_len``/``kv_start`` mask inputs
    (a masking change in the flash kernel, not a new kernel) to make
    attention block-diagonal, and chunk/tile boundaries are derived from
    the static segment width ``P`` so no tile straddles two prompts.
    Scanned families (MoE's per-token expert capacity) pack on the BATCH
    axis instead — ``(N, P)`` rows through the same scanned prefill
    (:func:`repro.models.transformer.prefill_batch_ragged` under the
    paged layout, whose rows are right-padded at start 0).
  * **Scatter** — each segment's cache rows land in its slot in one
    fused write (:func:`~repro.models.transformer.write_cache_slot_segments`
    dense / :func:`~repro.models.transformer.scatter_segments_to_pool`
    paged; dummy segments write a real slot that a later real segment
    overwrites, or the paged block-0 sink).  Per-segment health probes,
    first-token sampling (one vectorized call), and slot arming then
    mirror solo admission per segment, in FIFO order.
  * **Per-slot decode** — unchanged: the packed path only changes HOW a
    slot's rows were produced, never what they contain.

**Invariance contract.**  Every request's tokens are BIT-IDENTICAL to
solo per-request admission (``packed_prefill=False``): segment masking
yields exact-zero cross-segment contributions, segment-aligned chunking
reproduces the solo reduction geometry, RoPE positions stay relative to
each segment's own start, and the vectorized first-token sample uses the
same per-request keys (``tests/test_packed_prefill.py`` sweeps dense/moe
x dense/paged x xla/fused, shared prefixes in one pack, and mid-pack
faults/deadlines).  Differences are confined to bytes no computation
ever reads: pad rows beyond a segment's prompt (masked out of every
reduction; zero-filled in the dense scatter) and intra-pack prefix
sharing (two requests packed TOGETHER each compute their full prompt —
registration happens after the pack's health check — so shared-block
stats, not tokens, can differ from sequential admission).

``ServeEngine.warmup()`` drives synthetic traffic through every
``(bucket, num_prompts)`` bin plus the decode/sampler/health executables
and reports the compiled-executable census (:meth:`executable_counts`);
after it, steady-state serving over bucketable traffic never retraces —
CI-gated by the ``packed_warmup_steady_state`` analysis probe.  Prompts
whose power-of-two bucket cannot fit ``max_seq`` fall back to solo
admission (one extra signature each, exactly as today); recurrent
families always use solo admission.

Serving robustness contract
===========================
The serve loop is fault-isolating and always-admitting: a request can
arrive, expire, or go numerically toxic without touching any other
request's tokens, and every submitted request terminates with exactly one
structured :class:`ServeResult` — the loop itself never raises mid-stream
unless ``strict`` is on.

**Status taxonomy** (:class:`FinishReason`; every request gets exactly
one, delivered in a :class:`FinishEvent` and in ``ServeResult.finish``):

  ``EOS``       the request sampled its ``eos_id`` (output includes it)
  ``MAX_NEW``   the per-request token budget (clamped to ``max_seq``) ran
                out
  ``DEADLINE``  ``Request.deadline_ms`` (wall-clock ms since submission)
                or ``ServeConfig.max_queue_wait_ms`` (queue-wait cap)
                expired; an in-flight request is evicted with its partial
                output, a queued one finishes empty
  ``SHED``      admission refused: invalid request (empty / oversized
                prompt, ``max_new < 1``) under ``strict=False``, bounded-
                queue overflow (``ServeConfig.max_queue``), or a paged
                pool that can never satisfy the request
  ``FAULT``     the NaR quarantine tripped (below); partial output is
                returned

**NaR / non-finite quarantine.**  Posit arithmetic concentrates every
error into NaR, which dequantizes to NaN — so one in-device finiteness
reduction over each slot's last-position logits
(:func:`repro.models.transformer.logits_health`) catches a NaR (or float
Inf/NaN) anywhere in a slot's datapath.  The ``(B,)`` health bits ship
packed with the sampled tokens in the existing per-step transfer (no
extra device sync).  A slot whose probe goes False is evicted with
``FAULT`` *before* its garbage token is recorded, its paged blocks are
freed (and never registered for prefix sharing), and its partial output
is returned.  Because the model is batch-composition invariant (pad
masking, per-slot positions, per-request keys) and — for MoE — expert
capacity dispatch is per batch row, every other slot's tokens are
bit-identical to a fault-free run; ``tests/test_serve_faults.py`` asserts
this across dense/paged layouts.  ``ServeConfig.health_checks=False``
disables the sweep (the probe still computes in-device; its bit is
ignored).

**Deadlines** are wall-clock milliseconds measured from ``submit()``
(``serve()`` submits all requests up front).  Expiry is checked once per
decode step and once per admission sweep — resolution is therefore one
decode step, not a hard real-time bound.  The engine takes an injectable
``clock`` callable (seconds, default ``time.monotonic``) so tests drive
deadlines deterministically.

**Backpressure.**  ``ServeConfig.max_queue`` bounds the number of
requests waiting for a slot; ``submit()`` beyond it sheds (or raises
under ``strict``).  ``serve(requests)`` batch submission is exempt — the
caller already holds the whole list.

**Snapshot / restore.**  :meth:`ServeEngine.snapshot` captures the entire
serve session — scheduler, allocator (refcounts, free list, LRU park,
prefix table), per-slot host mirrors, per-request bookkeeping, and the
device cache leaves (``jax.device_get``) — as one picklable dict.
:meth:`ServeEngine.restore` on a compatible engine (same ``ModelConfig``,
params, and ``ServeConfig``; this is the caller's contract) rebuilds the
session so the remaining stream completes with BIT-IDENTICAL tokens:
decode state is exactly (cache leaves, ``pos``/``start``/``cur`` mirrors)
and sampling state is exactly (per-request key, step counter), all of
which the snapshot carries.  Deadline clocks are rebased on restore
(elapsed time is preserved, downtime does not count against a deadline).

``strict=True`` (``ServeConfig.strict`` or the per-call override)
restores the legacy raising behavior for tests and batch drivers that
prefer exceptions: invalid requests, queue overflow, and unsatisfiable
paged admissions raise ``ValueError`` instead of shedding.

Mesh-sharded serving (tensor parallel x data parallel)
======================================================
Passing ``mesh=`` (a single-axis ``("model",)`` Mesh, e.g. one entry of
:func:`repro.launch.mesh.serve_meshes`) turns the engine tensor-parallel:
every jitted executable above — decode step, bucketed/packed prefill,
slot scatter, the paged pool writers — is wrapped in ``shard_map`` over
the SAME per-arch partition specs the launch layer derives
(``param_pspecs`` / ``cache_pspecs``), so each TP shard runs the
unchanged kernels on its head/d_ff/vocab slice and the sharded engine is
the single-device engine times ``tp``, not a different program.

  * **Exact collectives only.**  The TP model path communicates solely
    through fixed-order ``all_gather`` combines (attention-out head
    groups, MLP ``d_ff`` groups, vocab-sharded embed owner-select and
    logits concat) — never ``psum``-style reductions whose ordering the
    compiler picks.  With ``ModelConfig.tp_groups`` pinning the
    contraction-group count, a TP engine's tokens are BIT-IDENTICAL to
    the unsharded engine (and hence to solo runs) for every feature
    above: dense/paged layouts, packed prefill, mid-flight admission,
    faults, snapshot/restore (a snapshot taken on one topology restores
    onto any other with the same ``tp_groups``).  The
    ``decode-collective-lint`` analysis rule walks the decode jaxpr and
    fails CI on any collective outside the ``all_gather`` allowlist.
  * **Resharding stays out of the hot loop.**  ``__init__`` computes the
    param/cache layouts ONCE (normalized so ``device_put`` placements
    and executable outputs share jit cache keys), places params, and
    every cache the session creates (:meth:`restore` included) through
    them.  Steady state is therefore zero-transfer and zero-retrace:
    :meth:`steady_layout_violations` asserts every live leaf still
    carries its precomputed sharding, and the ``sharded-steady-state``
    probe asserts a post-:meth:`warmup` serve compiles nothing new.
  * **Data parallelism** is replica routing, not batch sharding: a
    :class:`repro.serve.router.ReplicaRouter` owns N independent engines
    on disjoint device subsets (an unsharded replica takes ``device=``
    instead of a mesh and keeps its params and caches on that device),
    routes ``submit()`` least-loaded, and
    merges the per-replica streams behind the single-engine surface —
    aggregate throughput scales with replicas while per-request
    semantics (FinishReason, deadlines, quarantine, bit-identity) are
    each replica's own.  :func:`repro.serve.emit.stream_async` (CLI
    ``--emit-async``) moves consumer-side detokenize/emit cost off the
    decode thread behind a bounded queue.

Sharded serving currently requires the dense attention family,
``head_mode == "heads"`` (q and kv heads divisible by ``tp``), and
``tp_groups > 0``; ``tests/test_sharded_serve.py`` and the CI
``multi-device`` job (8 forced host devices, ``BENCH_PR10.json``) gate
the contract.

Spans, counters and executable names
====================================
Each solo admission runs inside a ``serve.admit`` profiler span
(``jax.profiler.TraceAnnotation``; args ``rid``, ``prompt`` tokens,
padded ``bucket`` length, ``wait_ms`` from submission) holding
``serve.prefill`` (prefill dispatch and health read), ``serve.scatter``
(the cache write) and ``serve.first_token`` (sample and host read); a
packed admission inside ``serve.admit_pack`` (the args summed over the
pack, plus ``segments``).  Each decode step runs inside ``serve.step``
(args ``step``, ``active`` slots, ``live_rows``, ``read_rows`` and
``scan_rows`` of KV) holding ``serve.dispatch`` (the host mirrors to the
device, five eager ``jit_convert_element_type`` executions, then the
decode and sample dispatch), ``serve.sync`` (the (B, 2) readback) and
``serve.record`` (the bookkeeping).  No span is open across a ``yield``, and every arg
comes from host state: a span never syncs the device, and with the
profiler off costs about a microsecond.  The spans land in the
profiler's trace on the device ops' clock, where every engine
executable shows as ``jit_serve_<name>``.  :meth:`ServeEngine.counters`
sums the same increments over sessions.

Static guarantees (proved, not sampled)
=======================================
``python -m repro.analysis`` (the CI ``static-analysis`` job) proves the
properties this engine's correctness rests on, before anything runs:

  * every divider datapath plan the numerics stack can select is PROVEN
    with exact rational arithmetic — selection containment (Eqs 26-29),
    residual-frame width, Table I scaling range, iteration/OTF register
    sufficiency (Eqs 18-19, 30-31) — so a config that validates cannot
    silently select an overflowing or under-iterated divider;
  * the jitted hot path (``_decode``/``_prefill``) carries no f64 avals
    and no host callbacks — nothing in the step can sync the device
    beyond the packed (B, 2) token/health transfer;
  * every posit-divide denominator reduces in fixed order (no
    compiler-ordered ``reduce_sum``), which is what makes the
    batch-composition invariance above hold bit-exactly;
  * serving a heterogeneous stream compiles exactly ONE decode
    executable per (family, backend) — the no-retrace contract of the
    slot design is probed by actually serving the admission-trap stream.
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import itertools
import time
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as PSpec

from repro.kernels.posit_flash_attn import decode_rows_scanned
from repro.models import transformer as T
from repro.models.config import ModelConfig


def _broadcast(value, n: int, dtype, what: str) -> np.ndarray:
    """Scalar-or-per-request ServeConfig field -> validated (n,) array."""
    arr = np.asarray(value, dtype)
    if arr.ndim == 0:
        return np.full(n, arr, dtype)
    if arr.shape != (n,):
        raise ValueError(f"per-request {what} has shape {arr.shape}; "
                         f"expected a scalar or ({n},)")
    return arr


def _bucket(n: int, max_seq: int) -> int:
    """Prompt-length bucket for admission prefills: the smallest power of
    two >= n (so the jitted prefill has O(log max_seq) signatures), falling
    back to the exact length when the bucket would not leave room for a
    single generated token.

    The ONE shared bucketing helper — legacy per-request planning
    (:meth:`ServeEngine._plan`) and the packing planner
    (:meth:`ServeEngine._pack_key`) must agree on bucket geometry, so both
    route through here.  Oversized prompts are clamped EXPLICITLY: a
    prompt that cannot fit ``max_seq`` with at least one generated token
    raises ``ValueError`` here instead of relying on a later shape error
    downstream."""
    if n + 1 > max_seq:
        raise ValueError(
            f"prompt length {n} cannot fit max_seq={max_seq} "
            "with at least one new token")
    p = 8
    while p < n:
        p *= 2
    return p if p + 1 <= max_seq else n


def _pow2_ceil(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor) (pack-size bucketing)."""
    p = floor
    while p < n:
        p *= 2
    return p


def _named_jit(name: str, fn, **kw):
    """``jax.jit`` under a stable name: the lowered module, and each of its
    executions in a profiler trace, is ``jit_serve_<name>`` (``<name>`` as
    :meth:`ServeEngine.executable_counts` keys it), never ``jit__lambda``."""
    fn.__name__ = fn.__qualname__ = f"serve_{name}"
    return jax.jit(fn, **kw)


class FinishReason(str, enum.Enum):
    """Terminal status of a served request (see module docstring)."""

    EOS = "eos"            # sampled its eos_id
    MAX_NEW = "max_new"    # token budget exhausted
    DEADLINE = "deadline"  # deadline_ms / max_queue_wait_ms expired
    SHED = "shed"          # refused at admission (overflow / invalid)
    FAULT = "fault"        # NaR / non-finite quarantine tripped


@dataclasses.dataclass
class ServeResult:
    """Structured terminal record for one request.

    ``tokens`` is always present (possibly empty / partial);
    ``queue_wait_ms``/``ttft_ms``/``latency_ms`` are wall-clock
    milliseconds (``ttft_ms`` is None when no token was ever produced).
    """

    rid: int
    tokens: np.ndarray
    finish: FinishReason
    detail: str = ""
    queue_wait_ms: float = 0.0
    ttft_ms: Optional[float] = None
    latency_ms: float = 0.0


#: The cumulative counters of :meth:`ServeEngine.counters`; each is also a
#: per-session ``last_serve_stats`` key.
COUNTERS = ("decode_steps", "active_slot_steps", "admissions",
            "prompt_tokens", "prefill_rows", "kv_rows_live", "kv_rows_read",
            "kv_rows_scanned")

#: Streaming events yielded by :meth:`ServeEngine.serve_stream`.
TokenEvent = collections.namedtuple("TokenEvent", ("rid", "token"))
FinishEvent = collections.namedtuple("FinishEvent", ("rid", "result"))


@dataclasses.dataclass
class ServeConfig:
    """Engine limits + default sampling parameters.

    ``temperature``/``eos_id`` accept a scalar (shared by all requests) or
    a per-request sequence matching the submitted batch; ``Request`` fields
    override either.  Build from a model config with :meth:`from_model`
    (``get_config(name, max_batch=..., max_seq=...)`` carries the serving
    overrides) instead of mutating instances ad hoc.
    """

    max_batch: int = 8
    max_seq: int = 512
    temperature: Union[float, Sequence[float]] = 0.0  # 0 = greedy
    eos_id: Union[int, Sequence[int]] = -1            # -1 = never stop early
    seed: int = 0
    # paged KV cache (see module docstring): "dense" keeps the per-slot
    # (B, max_seq) regions; "paged" switches pageable families to the
    # refcounted block pool with prefix sharing.
    kv_layout: str = "dense"
    block_size: int = 16                 # pool page rows (pow2, 8..128)
    num_blocks: Optional[int] = None     # pool size; None = worst case + sink
    # packed multi-prompt prefill (see "Packed multi-prompt prefill"
    # above): admission packs the queue head into (bucket, num_prompts)
    # bins served from shared executables; bit-identical to solo admission
    packed_prefill: bool = False
    # robustness knobs (see "Serving robustness contract" above)
    max_queue: Optional[int] = None          # submit() backpressure bound
    max_queue_wait_ms: Optional[float] = None  # queue-wait deadline for all
    strict: bool = False                 # legacy raising behavior
    health_checks: bool = True           # NaR / non-finite quarantine

    @classmethod
    def from_model(cls, cfg: ModelConfig, **overrides) -> "ServeConfig":
        kw = dict(max_batch=cfg.serve_max_batch, max_seq=cfg.serve_max_seq)
        kw.update(overrides)
        return cls(**kw)


@dataclasses.dataclass
class Request:
    """One generation request for the continuous scheduler.

    ``temperature``/``eos_id`` default to the engine's ``ServeConfig``
    values; ``seed`` pins the sampling-key id (defaults to the request's
    submission index) so sampled decoding reproduces across runs and batch
    compositions.  ``deadline_ms`` is a wall-clock budget in milliseconds
    from submission (None = no deadline): a request still queued or still
    decoding past it finishes ``DEADLINE`` with whatever it produced.
    """

    tokens: np.ndarray
    max_new: int = 32
    temperature: Optional[float] = None
    eos_id: Optional[int] = None
    seed: Optional[int] = None
    deadline_ms: Optional[float] = None


class BlockAllocator:
    """Refcounted KV block pool with prefix-hash reuse (host-side).

    Owns the id space ``[1, num_blocks)`` of a paged cache's pool (block 0
    is the reserved parked-slot sink and is never handed out).  Three block
    states:

      * **free** — on the free deque, contents meaningless.
      * **live** — ``refcount > 0``: mapped by one or more slot tables.
      * **cached** — refcount 0 but REGISTERED as a prefix block: parked in
        an LRU OrderedDict, still matchable by :meth:`match_prefix`, and
        reclaimed (unregistered + reused) by :meth:`alloc` only when the
        free deque is empty.

    Prefix identity is a rolling chain hash over full token blocks
    (``h_j = hash((h_{j-1}, block_j_tokens))``), with every table entry
    keeping the FULL prefix tuple — a match requires tuple equality, so a
    hash collision can cost a lookup but never alias two prefixes.  The
    ``hasher`` hook exists for the collision-safety test (inject a
    constant hash and watch matching still come out correct).
    """

    def __init__(self, num_blocks: int, block_size: int, hasher=None):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (sink + 1), got {num_blocks}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._hash = hash if hasher is None else hasher
        self.refcount = np.zeros(num_blocks, np.int64)
        self.free: collections.deque = collections.deque(range(1, num_blocks))
        self.cached: collections.OrderedDict = collections.OrderedDict()
        # chain hash -> [(full prefix tuple, block id), ...]; owner maps a
        # registered block back to its table entry for unregistration
        self.table = {}
        self.owner = {}
        self.hits = 0      # match_prefix calls that shared >= 1 block
        self.lookups = 0

    # ------------------------------------------------------------ lifecycle

    def alloc(self) -> int:
        """A fresh block at refcount 1; reclaims the LRU cached prefix
        block when the free deque is empty; clean ``ValueError`` when the
        pool is truly exhausted (every block live)."""
        if self.free:
            bid = self.free.popleft()
        elif self.cached:
            bid, _ = self.cached.popitem(last=False)     # LRU reclaim
            self._unregister(bid)
        else:
            raise ValueError(
                f"paged KV pool exhausted: all {self.num_blocks - 1} "
                "usable blocks are mapped by live requests")
        self.refcount[bid] = 1
        return bid

    def incref(self, bid: int) -> None:
        self.refcount[bid] += 1
        self.cached.pop(bid, None)       # reactivated from the LRU park

    def decref(self, bid: int) -> None:
        assert self.refcount[bid] > 0, bid
        self.refcount[bid] -= 1
        if self.refcount[bid] == 0:
            if bid in self.owner:
                self.cached[bid] = None  # registered: park, stay matchable
            else:
                self.free.append(bid)

    def quarantine(self, bid: int) -> None:
        """Fault path (call AFTER the owning slot's decref): a block owned
        by a quarantined slot may hold poisoned rows and must never be
        served to a future prefix match.  If it just parked (refcount 0),
        unregister it and return it straight to the free list; a block
        still shared (refcount > 0) stays — its other readers would trip
        their own health probes and quarantine in turn."""
        if self.refcount[bid] == 0 and bid in self.owner:
            self.cached.pop(bid, None)
            self._unregister(bid)
            self.free.append(bid)

    def blocks_in_use(self) -> int:
        return int((self.refcount > 0).sum())

    def _unregister(self, bid: int) -> None:
        h, full = self.owner.pop(bid)
        bucket = self.table[h]
        bucket[:] = [e for e in bucket if e[1] != bid]
        if not bucket:
            del self.table[h]

    # ------------------------------------------------------- prefix sharing

    def _chain(self, tokens):
        """Yield (chain hash, full prefix tuple, block index) per FULL
        token block of ``tokens``."""
        bs = self.block_size
        h = None
        for j in range(len(tokens) // bs):
            h = self._hash((h, tuple(tokens[j * bs:(j + 1) * bs])))
            yield h, tuple(tokens[:(j + 1) * bs]), j

    def match_prefix(self, tokens) -> List[int]:
        """Longest already-registered block chain for this prompt: block
        ids whose FULL token prefixes match (never hash-only)."""
        self.lookups += 1
        shared: List[int] = []
        for h, full, _ in self._chain(tokens):
            bid = next((b for p, b in self.table.get(h, ()) if p == full),
                       None)
            if bid is None:
                break
            shared.append(bid)
        if shared:
            self.hits += 1
        return shared

    def register_prefix(self, tokens, block_ids) -> None:
        """Publish this request's full-block chain for future sharing.
        First writer wins: a prefix already in the table keeps its original
        page (the duplicate storage stays unregistered and frees normally);
        a block registered under one prefix is never re-registered."""
        for h, full, j in self._chain(tokens):
            bid = int(block_ids[j])
            bucket = self.table.setdefault(h, [])
            if any(p == full for p, _ in bucket) or bid in self.owner:
                continue
            bucket.append((full, bid))
            self.owner[bid] = (h, full)


class Scheduler:
    """Slot bookkeeping for continuous batching: a FIFO request queue, slot
    admission/eviction, and the per-slot host-side state mirrored into the
    device-side ``pos``/``start``/sampling vectors.

    All per-step bookkeeping is vectorized over slots (numpy fancy
    indexing); Python iterates only over admission/eviction EVENTS, never
    over batch elements per token.
    """

    def __init__(self, n_slots: int, max_out: int):
        self.n = n_slots
        self.queue: collections.deque = collections.deque()
        self.active = np.zeros(n_slots, bool)
        self.slot_req = np.full(n_slots, -1, np.int64)
        self.out_buf = np.zeros((n_slots, max(max_out, 1)), np.int32)
        self.out_len = np.zeros(n_slots, np.int64)
        self.budget = np.zeros(n_slots, np.int64)

    def free_slots(self) -> np.ndarray:
        return np.flatnonzero(~self.active)

    def grow_out(self, max_out: int) -> None:
        """Widen the output buffer to hold ``max_out`` tokens per slot
        (live submission means the largest budget isn't known up front)."""
        cur = self.out_buf.shape[1]
        if max_out > cur:
            self.out_buf = np.pad(self.out_buf, ((0, 0), (0, max_out - cur)))

    def admit(self, slot: int, rid: int, max_new: int) -> None:
        self.grow_out(max_new)
        self.active[slot] = True
        self.slot_req[slot] = rid
        self.out_len[slot] = 0
        self.budget[slot] = max_new

    def record(self, tokens: np.ndarray, eos: np.ndarray):
        """Append this step's tokens for active slots; return the slots
        that just finished (EOS or budget).  Vectorized over slots."""
        act = self.active.copy()
        self.out_buf[act, self.out_len[act]] = tokens[act]
        self.out_len[act] += 1
        finished = act & ((tokens == eos) | (self.out_len >= self.budget))
        return np.flatnonzero(finished)

    def record_one(self, slot: int, token: int, eos_id: int) -> bool:
        """Append an admission-time (prefill-sampled) token for one slot;
        True if the request is already finished (EOS as its first token,
        or a budget of one)."""
        self.out_buf[slot, self.out_len[slot]] = token
        self.out_len[slot] += 1
        return token == eos_id or self.out_len[slot] >= self.budget[slot]

    def evict(self, slot: int) -> np.ndarray:
        out = self.out_buf[slot, : self.out_len[slot]].copy()
        self.active[slot] = False
        self.slot_req[slot] = -1
        return out

    @staticmethod
    def plan_packs(head):
        """Packing planner: group the queue head into admission packs.

        ``head`` is ``[(rid, bucket_len | None)]`` for AT MOST one queue
        entry per free slot, in FIFO order (``None`` marks an entry the
        engine cannot pack — exact-length bucket fallback, recurrent
        family).  Returns ``(packs, rest)``: ``packs`` is
        ``[(bucket_len, [rids])]`` grouping same-bucket entries in first-
        seen order, ``rest`` the unpackable rids in FIFO order.  Every
        head entry lands in exactly one of the two, and since the head is
        capped at the free-slot count, everything here would have been
        admitted THIS sweep under solo admission too — same-sweep
        regrouping never lets a request overtake one that would otherwise
        already be decoding.  Pack sizes are bucketed to powers of two by
        the admitter (dummy segments), not here; a pack of ONE is valid —
        it keeps singleton admissions on the same pre-compiled
        executables (the warmup no-retrace contract)."""
        packs: Dict[int, List[int]] = {}
        order: List[int] = []
        rest: List[int] = []
        for rid, key in head:
            if key is None:
                rest.append(rid)
                continue
            if key not in packs:
                packs[key] = []
                order.append(key)
            packs[key].append(rid)
        return [(key, packs[key]) for key in order], rest

    @property
    def any_active(self) -> bool:
        return bool(self.active.any())


class _ServeState:
    """One serve SESSION: everything the engine mutates between ``submit``
    and the last ``FinishEvent``.  A fresh state is created whenever a
    request is submitted to an idle engine, so request ids (and therefore
    default sampling-key ids) restart at 0 per session — matching the
    stream indices the pre-streaming ``serve()`` used.  ``snapshot()``
    serializes exactly this object (+ the device cache leaves)."""

    def __init__(self, eng: "ServeEngine", init_cache: bool = True):
        sc = eng.sc
        B = sc.max_batch
        # per-request bookkeeping (index = rid)
        self.reqs: List[Request] = []
        self.plans: List[Optional[tuple]] = []   # (P, start, budget) | None
        self.req_temp: List[float] = []
        self.req_eos: List[int] = []
        self.req_key: List[int] = []             # resolved sampling-key id
        self.queue: collections.deque = collections.deque()
        self.pending: List = []                  # events awaiting the stream
        self.results: Dict[int, ServeResult] = {}
        self.t_submit: Dict[int, float] = {}     # ms, engine clock
        self.t_admit: Dict[int, float] = {}
        self.ttft: Dict[int, float] = {}         # ms durations
        self.sched = Scheduler(B, 1)
        # device-facing per-slot state (host mirrors, shipped each step)
        self.pos = np.zeros(B, np.int32)
        self.start = np.zeros(B, np.int32)
        self.cur = np.zeros((B, 1), np.int32)
        self.temps = np.zeros(B, np.float32)
        self.eos = np.full(B, -1, np.int32)
        self.keys = np.zeros((B, 2), np.uint32)
        self.steps = np.zeros(B, np.int32)
        self.last_tok_ms = np.zeros(B, np.float64)
        # caches
        if eng._paged:
            self.cache = (eng._place_cache(
                T.init_paged_cache(eng.cfg, eng._num_blocks, sc.block_size))
                          if init_cache else None)
            self.alloc = BlockAllocator(eng._num_blocks, sc.block_size)
            self.bt_host = np.zeros((B, eng._max_blocks), np.int32)
            self.slot_blocks: List[List[int]] = [[] for _ in range(B)]
            self.mini_zeros: Dict[int, object] = {}
        else:
            self.cache = (eng._place_cache(T.init_cache(eng.cfg, B,
                                                        sc.max_seq))
                          if init_cache else None)
            self.mini_zero = None     # built lazily (first admission)
        # packed-prefill zero mini templates, keyed (batch, rows): prefill
        # is pure, so one zero cache per bin shape serves every pack
        self.packed_zeros: Dict[tuple, object] = {}
        # measured counters
        self.decode_steps = 0
        self.active_slot_steps = 0
        self.admissions = 0
        self.faults = 0
        self.deadline_evictions = 0
        self.shed = 0
        self.hit_tokens = 0
        self.fill_tokens = 0
        self.prompt_tokens = 0
        self.owned_total = 0
        self.shared_total = 0
        self.peak_blocks = 0
        self.packed_packs = 0        # packed admission dispatches
        self.packed_segments = 0     # real requests admitted packed
        self.packed_dummies = 0      # pad segments burned on pow2 rounding
        self.prefill_rows = 0        # padded prompt rows prefilled
        self.kv_rows_live = 0        # KV rows the active slots attend
        self.kv_rows_read = 0        # KV rows decode attention fetches
        self.kv_rows_scanned = 0     # KV rows in the tiles it scans
        self.token_lats: List[float] = []
        self.closed = False          # counters folded into the engine's

    @property
    def drained(self) -> bool:
        return not (self.pending or self.queue or self.sched.any_active)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params,
                 sc: Optional[ServeConfig] = None,
                 clock: Optional[Callable[[], float]] = None,
                 mesh: Optional[Mesh] = None,
                 device: Optional[jax.Device] = None):
        if mesh is not None and device is not None:
            raise ValueError("pass mesh= (tensor-parallel) or device= "
                             "(unsharded replica), not both")
        self.cfg = cfg
        self.sc = sc if sc is not None else ServeConfig.from_model(cfg)
        # injectable wall clock (seconds) so deadline tests run
        # deterministically without sleeping
        self._clock = time.monotonic if clock is None else clock

        # --------------------------------------- tensor-parallel serve mesh
        # With ``mesh`` (a single-axis ("model",) Mesh, e.g. one entry of
        # launch.mesh.serve_meshes), every model executable below is
        # shard_map'd over it: weights and KV pools are partitioned on
        # their head/vocab/ffn axes per launch.mesh's spec tables, each
        # shard runs the SAME kernels on its head slice, and the only
        # cross-shard ops are the fixed-order all-gathers in models/layers
        # — so decoded tokens are bit-identical to an unsharded engine
        # with the same ``cfg.tp_groups``.  Param and decode-state layouts
        # are precomputed HERE, once: the hot loop never reshards (the
        # analysis layout probe asserts this).
        self._mesh = mesh
        self._device = device
        self._tp = 1
        self._pspec = self._cspec = None
        self._param_sharding = self._cache_sharding = None
        mcfg = cfg
        if mesh is not None:
            # lazy: repro.launch imports repro.serve (launcher circularity)
            from repro.launch import mesh as MX
            if tuple(mesh.axis_names) != ("model",):
                raise ValueError(
                    f"serve mesh must be a single ('model',) axis mesh, got "
                    f"axes {tuple(mesh.axis_names)}; data parallelism is "
                    "expressed as ReplicaRouter replicas on disjoint "
                    "device subsets (launch.mesh.serve_meshes)")
            tp = int(mesh.shape["model"])
            if cfg.family != "dense":
                raise NotImplementedError(
                    f"tensor-parallel serving supports family='dense' "
                    f"(got {cfg.family!r}); run other families as "
                    "unsharded replicas behind a ReplicaRouter")
            if MX.head_mode(cfg, tp) != "heads":
                raise ValueError(
                    f"tp={tp} must divide n_heads={cfg.n_heads} and "
                    f"n_kv_heads={cfg.n_kv_heads} (head-sharded serving; "
                    "head_dim/repl-kv modes are training-only)")
            if not cfg.tp_groups:
                raise ValueError(
                    "sharded serving needs cfg.tp_groups > 0: contractions "
                    "over sharded dims combine in a fixed group order so "
                    "outputs are bit-identical across TP degrees — set the "
                    "SAME tp_groups on any reference engine you compare "
                    "against (e.g. tp_groups equal to the largest TP "
                    "degree you deploy)")
            self._tp = tp
            mcfg = cfg.replace(tp_axis="model", tp_size=tp)

            def strip(spec):
                # drop trailing Nones: executable outputs carry the elided
                # form, and jit keys on sharding EQUALITY — a full-rank
                # spec from device_put would retrace every executable once
                # per (fresh-template vs step-output) input
                parts = list(spec)
                while parts and parts[-1] is None:
                    parts.pop()
                return PSpec(*parts)

            def specs(tree):
                return jax.tree.map(strip, tree,
                                    is_leaf=lambda x: isinstance(x, PSpec))

            self._pspec = specs(MX.param_pspecs(cfg, params, mesh))
            # dense mini/full caches and paged pools share one tree
            # structure AND one spec (KV heads at leaf index 3)
            self._cspec = specs(MX.cache_pspecs(
                cfg, jax.eval_shape(lambda: T.init_cache(cfg, 1, 16)), mesh,
                batch_sharded=False))
            self._param_sharding = MX.named(mesh, self._pspec)
            self._cache_sharding = MX.named(mesh, self._cspec)
            params = jax.device_put(params, self._param_sharding)
        elif device is not None:
            # the jitted executables run where their committed params
            # live; host inputs are uncommitted and follow them
            params = jax.device_put(params, device)
        self.params = params
        self._mcfg = mcfg

        ps, cs, rr = self._pspec, self._cspec, PSpec()

        def sm(fn, in_specs, out_specs):
            # shard_map over the serve mesh; identity when unsharded.
            # check_vma=False: the decode body's collectives are the
            # fixed-order all-gathers in models/layers, whose replication
            # the checker cannot prove through lax.scan
            if mesh is None:
                return fn
            return shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

        self._sm = sm
        # the persistent cache is donated (argument 1 / 0): it is rebound on
        # every step, and donation keeps a compiled backend from copying the
        # whole B x max_seq multi-layer cache per decode step / admission.
        # _prefill must NOT donate: serve() reuses one zero mini-cache.
        # decode always computes the (B,) health probe in-device
        # (with_health=True): it rides the same jitted call and the same
        # host transfer, so fault detection costs no extra sync.
        self._decode = _named_jit(
            "decode",
            sm(lambda p, c, t, i, s: T.decode_step(p, mcfg, c, t, i, s,
                                                   with_health=True),
               (ps, cs, rr, rr, rr), (rr, cs, rr)),
            donate_argnums=1)
        self._prefill = _named_jit(
            "prefill",
            sm(lambda p, c, t, s: T.prefill(p, mcfg, {"tokens": t}, c, s),
               (ps, cs, rr, rr), (rr, cs)))
        self._write_slot = _named_jit(
            "write_slot",
            sm(lambda c, m, b: T.write_cache_slot(mcfg, c, m, b),
               (cs, cs, rr), cs),
            donate_argnums=0)
        self._sample_full = _named_jit(
            "sample_full", lambda *a: self._sample_impl(*a))
        self._sample_greedy = _named_jit(
            "sample_greedy", lambda lg: self._greedy_impl(lg))
        # packed serve-loop samplers: one (B, 2) int32 [token, healthy]
        self._sample_full_h = _named_jit(
            "sample_full_h", lambda *a: self._sample_h_impl(*a))
        self._sample_greedy_h = _named_jit(
            "sample_greedy_h", lambda lg, h: self._greedy_h_impl(lg, h))
        self._health = _named_jit(
            "health", lambda lg: T.logits_health(cfg, lg))
        self._base_key = jax.random.PRNGKey(self.sc.seed)
        self.last_serve_stats = None    # measured counters of the last serve
        self.last_results: Optional[List[ServeResult]] = None
        self._st: Optional[_ServeState] = None
        self._ended = dict.fromkeys(COUNTERS, 0)   # closed sessions' sums

        # ------------------------------------------------------ paged layout
        sc = self.sc
        if sc.kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {sc.kv_layout!r}")
        # the fused decode attention scans only each slot's live KV tiles
        # (the hybrid family's windowed ring attends through XLA)
        self._tile_scan = (cfg.attn_backend == "fused"
                           and cfg.family not in ("ssm", "hybrid"))
        # recurrent families keep O(1) state — nothing to page; they fall
        # back to the dense slot path (documented in the module docstring)
        self._paged = (sc.kv_layout == "paged"
                       and cfg.family in ("dense", "moe"))
        if self._paged:
            bs = sc.block_size
            if bs < 8 or bs > 128 or bs & (bs - 1):
                raise ValueError(
                    f"block_size must be a power of two in [8, 128] (kv "
                    f"kernel page constraint), got {bs}")
            if sc.max_seq % bs:
                raise ValueError(
                    f"max_seq={sc.max_seq} must be a multiple of "
                    f"block_size={bs} (virtual slot length = table width "
                    "* block size must equal the dense max_seq for "
                    "bit-identical tile geometry)")
            self._max_blocks = sc.max_seq // bs
            # worst case: every slot maps max_blocks own pages, + sink 0
            self._num_blocks = (sc.num_blocks if sc.num_blocks is not None
                                else sc.max_batch * self._max_blocks + 1)
            if self._num_blocks < 2:
                raise ValueError(f"num_blocks={self._num_blocks} < 2")
            # prefix sharing requires prefix pages to be a pure function of
            # the prefix tokens; a quantized cache stores rounded rows that
            # prefill does not attend, so sharing is disabled there
            self._share = not cfg.numerics.kv_cache_format
            self._decode_paged = _named_jit(
                "decode_paged",
                sm(lambda p, c, bt, t, i, s: T.decode_step(
                       p, mcfg, c, t, i, s, block_tables=bt,
                       with_health=True),
                   (ps, cs, rr, rr, rr, rr), (rr, cs, rr)),
                donate_argnums=1)
            # static args cannot pass through shard_map: close over them
            # inside the jit trace (one shard_map per static value, cached
            # by the jit signature exactly as before)
            self._prefill_t0 = _named_jit(
                "prefill_t0",
                lambda p, c, t, s, t0: sm(
                    lambda p_, c_, t_, s_: T.prefill(
                        p_, mcfg, {"tokens": t_}, c_, s_, t0),
                    (ps, cs, rr, rr), (rr, cs))(p, c, t, s),
                static_argnums=4)
            self._write_blocks = _named_jit(
                "write_blocks",
                sm(lambda c, m, bids, first: T.write_cache_blocks(
                       mcfg, c, m, bids, first),
                   (cs, cs, rr, rr), cs),
                donate_argnums=0)
            self._mini_prefix = _named_jit(
                "mini_prefix",
                lambda c, bids, rows: sm(
                    lambda c_, b_: T.mini_cache_with_prefix(mcfg, c_, b_,
                                                            rows),
                    (cs, rr), cs)(c, bids),
                static_argnums=2)
            self._scatter_pool = _named_jit(
                "scatter_pool",
                sm(lambda c, d, bt: T.scatter_dense_to_pool(mcfg, c, d, bt),
                   (cs, cs, rr), cs),
                donate_argnums=0)

        # -------------------------------------------- packed admission path
        # (see "Packed multi-prompt prefill" in the module docstring);
        # recurrent families keep solo admission — their O(1) state has no
        # ragged prefill to amortize
        self._packed = (bool(sc.packed_prefill)
                        and cfg.family in ("dense", "moe"))
        if self._packed:
            # dense family: N prompts concatenated into ONE (1, N*P)
            # sequence, block-diagonal via segment ids; seg_len is static
            # (chunk/tile geometry derives from it)
            self._prefill_packed = _named_jit(
                "prefill_packed",
                lambda p, c, t, pos, seg, last, P: sm(
                    lambda p_, c_, t_, pos_, seg_, last_: T.prefill_packed(
                        p_, mcfg, t_, c_, pos_, seg_, last_, P),
                    (ps, cs, rr, rr, rr, rr), (rr, cs))(p, c, t, pos, seg,
                                                        last),
                static_argnums=6)
            if self._paged:
                # segment rows -> per-segment pool blocks in one scatter
                self._scatter_segments = _named_jit(
                    "scatter_segments",
                    lambda c, m, bids, P: sm(
                        lambda c_, m_, b_: T.scatter_segments_to_pool(
                            mcfg, c_, m_, b_, P),
                        (cs, cs, rr), cs)(c, m, bids),
                    donate_argnums=0, static_argnums=3)
                # scanned families (moe): batch-axis pack, right-padded
                # rows at start 0 with per-row last-logit capture
                self._prefill_ragged = _named_jit(
                    "prefill_ragged",
                    sm(lambda p, c, t, s, last: T.prefill_batch_ragged(
                           p, mcfg, t, c, s, last),
                       (ps, cs, rr, rr, rr), (rr, cs)))
            else:
                # one fused write of every segment into its slot (rows
                # beyond the segment zero-fill, matching the solo mini)
                self._write_slot_segments = _named_jit(
                    "write_slot_segments",
                    lambda c, m, slots, P: sm(
                        lambda c_, m_, s_: T.write_cache_slot_segments(
                            mcfg, c_, m_, s_, P),
                        (cs, cs, rr), cs)(c, m, slots),
                    donate_argnums=0, static_argnums=3)
                # scanned families: (N, P) rows through the existing
                # batch-capable _prefill, scattered row-per-slot
                self._write_slots = _named_jit(
                    "write_slots",
                    sm(lambda c, m, slots: T.write_cache_slots(mcfg, c, m,
                                                               slots),
                       (cs, cs, rr), cs),
                    donate_argnums=0)

    # ------------------------------------------------------------- sampling

    def _masked_logits(self, lg):
        # last position only; never emit padded-vocab ids
        lg = lg[:, -1].astype(jnp.float32)
        return lg.at[:, self.cfg.vocab:].set(-1e30)

    def _greedy_impl(self, lg):
        return jnp.argmax(self._masked_logits(lg), axis=-1
                          ).astype(jnp.int32)[:, None]

    def _sample_impl(self, lg, temps, keys, steps):
        """Vectorized per-slot sampler, one jitted call per step.

        ``lg``: (B, S, V) logits (last position used); ``temps``: (B,)
        per-slot temperature (<= 0 means greedy); ``keys``: (B, 2) uint32
        per-REQUEST PRNG keys; ``steps``: (B,) per-request sample counter
        folded into the key, so a request draws the same stream regardless
        of which slot or global step it lands on.
        """
        lg = self._masked_logits(lg)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

        def draw(key, step, row, t):
            k = jax.random.fold_in(key, step)
            return jax.random.categorical(k, row / jnp.maximum(t, 1e-6))

        sampled = jax.vmap(draw)(keys, steps, lg, temps).astype(jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)[:, None]

    def _greedy_h_impl(self, lg, health):
        tok = self._greedy_impl(lg)[:, 0]
        return jnp.stack([tok, health.astype(jnp.int32)], axis=1)

    def _sample_h_impl(self, lg, health, temps, keys, steps):
        tok = self._sample_impl(lg, temps, keys, steps)[:, 0]
        return jnp.stack([tok, health.astype(jnp.int32)], axis=1)

    def _sample(self, lg, temps_np, keys, steps):
        """Jitted sampler dispatch: all-greedy batches skip the per-row
        categorical (greedy rows argmax identically on both paths, so the
        shortcut cannot change any request's tokens).

        NB ``jnp.array`` (copying), never ``jnp.asarray``: on the CPU
        backend ``asarray`` zero-copies host numpy buffers, and the serve
        loop mutates its per-slot state in place — an async-dispatched
        step could otherwise read the NEXT step's values (a real, rarely-
        firing race).
        """
        if not np.any(np.asarray(temps_np) > 0.0):
            return self._sample_greedy(lg)
        return self._sample_full(lg, jnp.array(temps_np, jnp.float32),
                                 keys, steps)

    def _sample_packed(self, lg, health, temps_np, keys, steps):
        """Serve-loop sampler: (B, 2) int32 ``[token, healthy]`` — the
        health bit rides the token transfer, no second device sync.  Token
        values are identical to :meth:`_sample` (same impls)."""
        if not np.any(np.asarray(temps_np) > 0.0):
            return self._sample_greedy_h(lg, health)
        return self._sample_full_h(lg, health,
                                   jnp.array(temps_np, jnp.float32),
                                   keys, steps)

    def _request_key(self, rid: int):
        return jax.random.fold_in(self._base_key, rid)

    # ------------------------------------------------ sharded-layout helpers

    def _place_cache(self, cache):
        """Commit a cache tree (full, mini, pool — same structure) to the
        engine's precomputed KV sharding, or to its ``device``; identity
        on unplaced engines.
        Every cache template passes through here at CREATION, so the hot
        loop's donated executables see exactly the layout they were
        compiled for and never reshard implicitly."""
        if self._mesh is not None:
            return jax.device_put(cache, self._cache_sharding)
        if self._device is not None:
            return jax.device_put(cache, self._device)
        return cache

    def load(self) -> int:
        """Routing load for the ReplicaRouter: active slots + queued
        requests of the live session (0 for an idle engine)."""
        st = self._st
        if st is None or st.drained:
            return 0
        return int(st.sched.active.sum()) + len(st.queue)

    def steady_layout_violations(self) -> List[str]:
        """Layout probe (sharded engines): every live param/cache leaf must
        still carry the sharding precomputed at construction — a non-empty
        return means some step introduced an implicit reshard into the hot
        loop.  Unsharded engines trivially report []."""
        if self._mesh is None:
            return []
        out: List[str] = []

        def chk(what, tree, shardings):
            def leaf(path, a, ns):
                # is_equivalent_to, not ==: a committed array may carry a
                # spec with trailing Nones elided, which partitions
                # identically
                if not a.sharding.is_equivalent_to(ns, a.ndim):
                    out.append(f"{what}{jax.tree_util.keystr(path)}: "
                               f"{a.sharding} != {ns}")
            jax.tree_util.tree_map_with_path(leaf, tree, shardings)

        chk("params", self.params, self._param_sharding)
        if self._st is not None and self._st.cache is not None:
            chk("cache", self._st.cache, self._cache_sharding)
        return out

    def decode_jaxpr(self):
        """The decode-step jaxpr (paged or dense, whichever this engine
        serves with), traced at the live signature — the analysis
        collective lint walks this to assert the sharded hot path contains
        ONLY the planned exact all-gathers (attention/MLP group combines,
        embed row exchange, logits concat) and no reduction collectives."""
        sc = self.sc
        B = sc.max_batch
        sds = jax.ShapeDtypeStruct
        p = jax.tree.map(lambda a: sds(a.shape, a.dtype), self.params)
        tok = sds((B, 1), jnp.int32)
        vec = sds((B,), jnp.int32)
        if self._paged:
            cache = jax.eval_shape(lambda: T.init_paged_cache(
                self.cfg, self._num_blocks, sc.block_size))
            bt = sds((B, self._max_blocks), jnp.int32)
            return self._decode_paged.trace(p, cache, bt, tok, vec,
                                            vec).jaxpr
        cache = jax.eval_shape(lambda: T.init_cache(self.cfg, B,
                                                    sc.max_seq))
        return self._decode.trace(p, cache, tok, vec, vec).jaxpr

    def _now_ms(self) -> float:
        return self._clock() * 1e3

    def executable_counts(self) -> Dict[str, int]:
        """Compiled-executable census over every jitted engine callable
        (the steady-state no-retrace probes diff this across a serve)."""
        fns = {
            "decode": self._decode,
            "prefill": self._prefill,
            "write_slot": self._write_slot,
            "sample_full": self._sample_full,
            "sample_greedy": self._sample_greedy,
            "sample_full_h": self._sample_full_h,
            "sample_greedy_h": self._sample_greedy_h,
            "health": self._health,
        }
        if self._paged:
            fns.update(decode_paged=self._decode_paged,
                       prefill_t0=self._prefill_t0,
                       write_blocks=self._write_blocks,
                       mini_prefix=self._mini_prefix,
                       scatter_pool=self._scatter_pool)
        if self._packed:
            fns.update(prefill_packed=self._prefill_packed)
            if self._paged:
                fns.update(scatter_segments=self._scatter_segments,
                           prefill_ragged=self._prefill_ragged)
            else:
                fns.update(write_slot_segments=self._write_slot_segments,
                           write_slots=self._write_slots)
        return {k: f._cache_size() for k, f in fns.items()}

    def counters(self) -> Dict[str, int]:
        """Cumulative serving counters over every session this engine has
        served, the live one included (:meth:`warmup`'s are left out):

          ``decode_steps``       decode steps run
          ``active_slot_steps``  active slots, summed over decode steps
          ``admissions``         admission prefills (quarantined ones too)
          ``prompt_tokens``      real prompt tokens of admitted requests
          ``prefill_rows``       prompt rows prefilled, bucket padding and
                                 a pack's dummy segments included
          ``kv_rows_live``       KV rows the active slots attend
                                 (``pos - start + 1`` each), summed over
                                 decode steps
          ``kv_rows_read``       KV rows decode attention fetches
                                 (``max_batch * max_seq`` a step)
          ``kv_rows_scanned``    KV rows in the tiles decode attention
                                 scans, over every slot (the flash
                                 kernel's live tiles; ``kv_rows_read``
                                 under the XLA backend)

        A session's own counts are its ``last_serve_stats``; the same
        increments are the args of the ``serve.admit`` and ``serve.step``
        spans (see :meth:`serve_stream`)."""
        out = dict(self._ended)
        st = self._st
        if st is not None and not st.closed:
            for k in COUNTERS:
                out[k] += getattr(st, k)
        return out

    def _close(self, st: _ServeState) -> None:
        """Fold an ended session's counters into the engine's totals
        (once, whether its stream ran to the end or was abandoned)."""
        if not st.closed:
            st.closed = True
            for k in COUNTERS:
                self._ended[k] += getattr(st, k)

    # ------------------------------------------------------- static batching

    def generate(self, prompts: List[np.ndarray], max_new: int = 32,
                 temperature=None, eos_id=None, seeds=None,
                 strict: Optional[bool] = None) -> List[np.ndarray]:
        """Serve one static batch to completion (all prompts admitted
        together, left-padded to the longest; slots idle after their EOS).
        prompts: list of 1D int32 token arrays (<= max_batch).  For
        streams longer than one batch — or mixed lengths that would idle
        slots — use :meth:`serve`.

        ``temperature``/``eos_id`` override the config defaults for this
        call (scalar or one per prompt); ``seeds`` pins each prompt's
        sampling-key id (defaults to the batch index), letting a sampled
        request reproduce its :meth:`serve` stream (same ``Request.seed``).

        Under ``strict=True`` (or ``ServeConfig.strict``) an oversized
        batch / empty prompt / oversized prompt raises ``ValueError`` as
        before; under ``strict=False`` (the default) invalid prompts are
        SHED — their output is empty, their batch row decodes a dummy
        token (batch invariance keeps the other rows bit-identical), and
        ``self.last_results`` carries the per-prompt :class:`ServeResult`.
        """
        sc = self.sc
        strict = sc.strict if strict is None else strict
        B = len(prompts)
        self.last_results = None
        if B == 0:
            return []
        shed: Dict[int, str] = {}
        if B > sc.max_batch:
            if strict:
                raise ValueError(
                    f"{B} prompts exceed max_batch={sc.max_batch}; submit "
                    f"them through serve(), which queues onto free slots")
            for i in range(sc.max_batch, B):
                shed[i] = (f"{B} prompts exceed max_batch={sc.max_batch}; "
                           "overflow shed (use serve() to queue)")
            prompts = prompts[:sc.max_batch]
        if strict and min(len(p) for p in prompts) == 0:
            raise ValueError("prompts must be non-empty")
        work = list(prompts)
        for i, p in enumerate(work):
            if len(p) == 0:
                shed[i] = "prompt must be non-empty"
            elif len(p) + 1 > sc.max_seq:
                if strict:
                    raise ValueError(
                        f"prompt length {len(p)} leaves no room to generate "
                        f"within max_seq={sc.max_seq}")
                shed[i] = (f"prompt length {len(p)} leaves no room to "
                           f"generate within max_seq={sc.max_seq}")
            if i in shed:
                # dummy row: decodes alongside the batch; batch invariance
                # (pad masking, per-slot state) keeps other rows bit-equal
                work[i] = np.array([1], np.int32)
        plen = max(len(p) for p in work)
        if strict and plen + 1 > sc.max_seq:
            raise ValueError(
                f"prompt length {plen} leaves no room to generate within "
                f"max_seq={sc.max_seq}")

        def _results(outs, n_prompts):
            res = []
            for i in range(n_prompts):
                if i in shed:
                    res.append(ServeResult(i, np.zeros(0, np.int32),
                                           FinishReason.SHED, shed[i]))
                else:
                    o = outs[i]
                    fin = (FinishReason.EOS
                           if o.size and o[-1] == eos_arr[i]
                           else FinishReason.MAX_NEW)
                    res.append(ServeResult(i, o, fin))
            return res

        eos_arr = _broadcast(sc.eos_id if eos_id is None else eos_id,
                             len(work), np.int32, "eos_id")
        if max_new < 1:
            outs = [np.zeros(0, np.int32) for _ in range(B)]
            self.last_results = [
                ServeResult(i, outs[i],
                            FinishReason.SHED if i in shed
                            else FinishReason.MAX_NEW,
                            shed.get(i, "max_new < 1"))
                for i in range(B)]
            return outs
        # per-batch max-token clamp against the cache size
        max_new = min(max_new, sc.max_seq - plen)

        Bw = len(work)
        temps = _broadcast(sc.temperature if temperature is None
                           else temperature, Bw, np.float32, "temperature")
        eos = eos_arr
        key_ids = range(Bw) if seeds is None else seeds
        keys = jnp.stack([self._request_key(i) for i in key_ids])

        # left-pad to align decode positions; start[b] = first real slot,
        # so pad positions can be masked out downstream
        toks = np.zeros((Bw, plen), np.int32)
        starts = np.zeros(Bw, np.int32)
        for i, p in enumerate(work):
            toks[i, plen - len(p):] = p
            starts[i] = plen - len(p)
        start = jnp.asarray(starts)

        cache = self._place_cache(T.init_cache(self.cfg, Bw, sc.max_seq))

        # whole-prompt prefill in one jitted call (chunked attention for
        # dense, scanned decode for the rest) — not plen dispatches
        lg, cache = self._prefill(self.params, cache, jnp.asarray(toks),
                                  start)

        if self._paged:
            # A/B path: identical dense prefill (bit-identity by
            # construction), then re-lay the rows out blockwise into a
            # pool with identity tables and decode paged.  Same virtual
            # length (max_blocks * block_size = max_seq) -> same kernel
            # tile geometry -> bit-identical decode.
            mb = self._max_blocks
            bt = jnp.asarray(
                1 + np.arange(Bw * mb, dtype=np.int32).reshape(Bw, mb))
            pool = self._place_cache(
                T.init_paged_cache(self.cfg, Bw * mb + 1, sc.block_size))
            cache = self._scatter_pool(pool, cache, bt)

        steps = jnp.zeros((Bw,), jnp.int32)
        cur = self._sample(lg, temps, keys, steps)
        emitted = []
        done = np.zeros(Bw, bool)
        for step in range(max_new):
            tok_h = np.asarray(cur[:, 0])   # ONE (B,) transfer per step
            emitted.append(tok_h)
            done |= tok_h == eos            # vectorized EOS tracking
            if done.all() or step == max_new - 1:
                break
            pos = jnp.full((Bw,), plen + step, jnp.int32)
            if self._paged:
                lg, cache, _h = self._decode_paged(self.params, cache, bt,
                                                   cur, pos, start)
            else:
                lg, cache, _h = self._decode(self.params, cache, cur, pos,
                                             start)
            steps = steps + 1
            cur = self._sample(lg, temps, keys, steps)
        mat = np.stack(emitted, axis=1)     # (B, <=max_new)
        outs = []
        for i in range(Bw):
            if i in shed:
                outs.append(np.zeros(0, np.int32))
                continue
            hits = np.flatnonzero(mat[i] == eos[i])
            end = hits[0] + 1 if hits.size else mat.shape[1]
            outs.append(mat[i, :end].astype(np.int32))
        outs += [np.zeros(0, np.int32)] * (B - Bw)   # overflow-shed tail
        self.last_results = _results(outs, B)
        return outs

    def serve_static(self, requests: Sequence,
                     max_new: int = 32) -> List[np.ndarray]:
        """Static-batch baseline: group requests into ``max_batch`` batches
        in arrival order and run each batch to completion with the group's
        LARGEST budget — a request only stops early at its own ``eos_id``,
        so short-budget members over-generate and slots idle.  That waste
        is exactly the scheduler-less behavior :meth:`serve` replaces (this
        stays as the A/B side of the decode-throughput benchmark and
        launcher).  Per-request ``temperature``/``eos_id``/``seed`` are
        honored; per-request ``max_new`` is not (by construction)."""
        reqs = [r if isinstance(r, Request)
                else Request(np.asarray(r, np.int32), max_new=max_new)
                for r in requests]
        n = len(reqs)
        def_temp = _broadcast(self.sc.temperature, n, np.float32,
                              "temperature")
        def_eos = _broadcast(self.sc.eos_id, n, np.int32, "eos_id")
        outs: List[np.ndarray] = []
        for i in range(0, n, self.sc.max_batch):
            group = list(enumerate(reqs[i:i + self.sc.max_batch], start=i))
            outs += self.generate(
                [r.tokens for _, r in group],
                max_new=max(r.max_new for _, r in group),
                temperature=[r.temperature if r.temperature is not None
                             else def_temp[j] for j, r in group],
                eos_id=[r.eos_id if r.eos_id is not None else def_eos[j]
                        for j, r in group],
                seeds=[r.seed if r.seed is not None else j
                       for j, r in group])
        return outs

    # --------------------------------------------------- continuous batching

    def _plan(self, r: Request) -> tuple:
        """Validate one request -> admission plan ``(P, start, budget)``.
        Raises ``ValueError`` (caller decides raise vs shed)."""
        sc = self.sc
        plen = len(r.tokens)
        if plen == 0:
            raise ValueError("prompt is empty")
        if plen + 1 > sc.max_seq:
            raise ValueError(
                f"prompt length {plen} cannot fit max_seq={sc.max_seq} "
                "with at least one new token")
        if r.max_new < 1:
            raise ValueError(f"max_new={r.max_new} < 1")
        # the budget clamp must match generate()'s (max_seq - plen) so a
        # request emits the same number of tokens either way: when the
        # power-of-two bucket's pad rows would eat into that budget,
        # admit at the exact prompt length instead (one extra jit
        # signature, but no silent truncation)
        budget = min(r.max_new, sc.max_seq - plen)
        if self._paged:
            # paged admission prefills UNPADDED at start 0: prefix
            # pages must be a pure function of the prefix tokens (the
            # sharing contract), which left-pad offsets would break.
            # One jit signature per (plen, t0) pair instead of per
            # bucket — the price of content-addressable pages.
            return (plen, 0, budget)
        P = _bucket(plen, sc.max_seq)
        if sc.max_seq - P < budget:
            P = plen
        return (P, P - plen, budget)

    def _scalar_default(self, value, what: str, dtype):
        arr = np.asarray(value)
        if arr.ndim != 0:
            raise ValueError(
                f"per-request ServeConfig {what} (a sequence) only works "
                f"through serve(), which resolves it by stream index; "
                f"submit() needs Request.{what} or a scalar default")
        return dtype(arr)

    def _register(self, st: _ServeState, r: Request) -> int:
        """Append request-level bookkeeping; returns its rid."""
        rid = len(st.reqs)
        st.reqs.append(r)
        st.plans.append(None)
        st.req_temp.append(
            float(r.temperature) if r.temperature is not None
            else self._scalar_default(self.sc.temperature, "temperature",
                                      float))
        st.req_eos.append(
            int(r.eos_id) if r.eos_id is not None
            else self._scalar_default(self.sc.eos_id, "eos_id", int))
        st.req_key.append(r.seed if r.seed is not None else rid)
        st.t_submit[rid] = self._now_ms()
        return rid

    def _finish(self, st: _ServeState, rid: int, tokens,
                reason: FinishReason, detail: str, now: float) -> ServeResult:
        t_sub = st.t_submit.get(rid, now)
        res = ServeResult(
            rid=rid, tokens=np.asarray(tokens, np.int32), finish=reason,
            detail=detail,
            queue_wait_ms=max(0.0, st.t_admit.get(rid, now) - t_sub),
            ttft_ms=st.ttft.get(rid),
            latency_ms=max(0.0, now - t_sub))
        st.results[rid] = res
        return res

    def submit(self, request, max_new: int = 32,
               strict: Optional[bool] = None, _bounded: bool = True) -> int:
        """Queue one request onto the live engine; returns its rid.

        Can be called before :meth:`serve_stream` or *while* a stream is
        being consumed — the request is admitted into the next freed slot.
        A request submitted to an IDLE engine (previous stream fully
        drained) starts a fresh session: rids — and therefore default
        sampling-key ids — restart at 0.

        Invalid requests and queue overflow raise under ``strict`` and
        SHED otherwise (the :class:`FinishEvent` is delivered by the
        stream; the :class:`ServeResult` is also immediately final).
        """
        sc = self.sc
        strict = sc.strict if strict is None else strict
        r = (request if isinstance(request, Request)
             else Request(np.asarray(request, np.int32), max_new=max_new))
        if self._st is None or self._st.drained:
            if self._st is not None:
                self._close(self._st)
            self._st = _ServeState(self)
        st = self._st
        try:
            plan = self._plan(r)
        except ValueError as e:
            if strict:
                raise
            rid = self._register(st, r)
            st.shed += 1
            res = self._finish(st, rid, np.zeros(0, np.int32),
                               FinishReason.SHED, str(e), self._now_ms())
            st.pending.append(FinishEvent(rid, res))
            return rid
        if (_bounded and sc.max_queue is not None
                and len(st.queue) >= sc.max_queue):
            msg = (f"queue overflow: {len(st.queue)} requests already "
                   f"queued (max_queue={sc.max_queue})")
            if strict:
                raise ValueError(msg)
            rid = self._register(st, r)
            st.shed += 1
            res = self._finish(st, rid, np.zeros(0, np.int32),
                               FinishReason.SHED, msg, self._now_ms())
            st.pending.append(FinishEvent(rid, res))
            return rid
        rid = self._register(st, r)
        st.plans[rid] = plan
        st.queue.append(rid)
        return rid

    def serve(self, requests: Sequence, max_new: int = 32,
              strict: Optional[bool] = None) -> List[np.ndarray]:
        """Serve a request stream with continuous batching.

        ``requests``: a sequence of :class:`Request` or raw 1D int32 token
        arrays (wrapped with ``max_new`` and the config's sampling
        defaults).  Any number of requests — they queue onto the engine's
        ``max_batch`` slots, each slot freed and re-admitted the moment its
        request finishes.  Returns outputs in request order (a shed /
        faulted / expired request yields its — possibly empty — partial
        output); ``self.last_results`` carries the per-request
        :class:`ServeResult` records and ``self.last_serve_stats`` the
        measured scheduler/SLO counters.  For token-level streaming and
        live admission use :meth:`submit` + :meth:`serve_stream` directly
        (this method is that loop, drained to completion).
        """
        sc = self.sc
        strict = sc.strict if strict is None else strict
        reqs: List[Request] = []
        for r in requests:
            if not isinstance(r, Request):
                r = Request(np.asarray(r, np.int32), max_new=max_new)
            reqs.append(r)
        n = len(reqs)
        if n == 0:
            return []
        # resolve sequence-valued config defaults by stream index (the
        # legacy per-request ServeConfig contract) onto the requests
        def_temp = _broadcast(sc.temperature, n, np.float32, "temperature")
        def_eos = _broadcast(sc.eos_id, n, np.int32, "eos_id")
        reqs = [dataclasses.replace(
                    r,
                    temperature=(r.temperature if r.temperature is not None
                                 else float(def_temp[i])),
                    eos_id=(r.eos_id if r.eos_id is not None
                            else int(def_eos[i])))
                for i, r in enumerate(reqs)]
        if strict:
            # legacy semantics: validate the WHOLE batch before any work
            for i, r in enumerate(reqs):
                try:
                    self._plan(r)
                except ValueError as e:
                    raise ValueError(f"request {i}: {e}") from None
        # batch submission is exempt from max_queue backpressure: the
        # caller already holds the full list (bound applies to submit())
        rids = [self.submit(r, strict=False, _bounded=False) for r in reqs]
        st = self._st
        for _ in self.serve_stream(strict=strict):
            pass
        self.last_results = [st.results[rid] for rid in rids]
        return [st.results[rid].tokens for rid in rids]

    # ------------------------------------------------------------ admission

    def _queue_limit(self, st: _ServeState, rid: int) -> Optional[float]:
        limits = [x for x in (st.reqs[rid].deadline_ms,
                              self.sc.max_queue_wait_ms) if x is not None]
        return min(limits) if limits else None

    def _admit_span(self, st: _ServeState, rid: int, P: int):
        """The ``serve.admit`` span of one solo admission: the request's id,
        its real prompt tokens, its padded length ``P`` and how long it
        waited from submission (engine clock).  Counts the prefill rows."""
        st.prefill_rows += P
        return TraceAnnotation(
            "serve.admit", rid=rid, prompt=len(st.reqs[rid].tokens),
            bucket=P, wait_ms=self._now_ms() - st.t_submit[rid])

    def _healthy(self, lg) -> bool:
        """Solo admission's health probe; reading it back is the host sync
        that waits out the prefill."""
        return (not self.sc.health_checks
                or bool(np.asarray(self._health(lg))[0]))

    def _admission_fault(self, st: _ServeState, rid: int) -> FinishEvent:
        """The request's own prompt already produces NaR/non-finite logits:
        quarantine it at admission, before anything of it is published."""
        now = self._now_ms()
        st.t_admit[rid] = now
        st.faults += 1
        res = self._finish(st, rid, np.zeros(0, np.int32),
                           FinishReason.FAULT,
                           "non-finite prefill logits quarantined", now)
        return FinishEvent(rid, res)

    def _admit_dense(self, st: _ServeState, slot: int, rid: int) -> List:
        sc = self.sc
        P, s0, budget = st.plans[rid]
        r = st.reqs[rid]
        with self._admit_span(st, rid, P):
            if st.mini_zero is None:
                # zero batch=1 cache reused by every admission (prefill is
                # pure, so the template never holds a previous request's
                # rows)
                st.mini_zero = self._place_cache(
                    T.init_cache(self.cfg, 1, sc.max_seq))
            toks = np.zeros((1, P), np.int32)
            toks[0, s0:] = r.tokens
            # prefill into a fresh (zero) batch=1 cache, then scatter it
            # into the freed slot — the other slots keep their rows and
            # state and never stop decoding
            with TraceAnnotation("serve.prefill"):
                lg, mini = self._prefill(self.params, st.mini_zero,
                                         jnp.asarray(toks),
                                         jnp.asarray([s0], jnp.int32))
                st.admissions += 1
                healthy = self._healthy(lg)
            if not healthy:
                # the poisoned mini cache is discarded, never scattered
                # into the shared slot cache
                return [self._admission_fault(st, rid)]
            with TraceAnnotation("serve.scatter"):
                st.cache = self._write_slot(st.cache, mini, jnp.int32(slot))
            st.prompt_tokens += len(r.tokens)
            return self._finish_admission(st, slot, rid, lg, P, s0, budget)

    def _admit_paged(self, st: _ServeState, slot: int, rid: int):
        """Paged admission; ``(False, [])`` = not enough free blocks
        (deferred).

        Maps the longest registered prefix (full blocks only), gathers
        it — plus a partially-shared CoW source block, NOT increfed:
        its copy is rewritten into an owned page — into a dense mini
        cache, prefills just the suffix from ``t0``, scatters the owned
        blocks into the pool, and registers the new chain.
        """
        sc = self.sc
        alloc = st.alloc
        plen, _, budget = st.plans[rid]
        r = st.reqs[rid]
        bs = sc.block_size
        total = -(-plen // bs)          # blocks covering rows [0, plen)
        toks = tuple(int(t) for t in r.tokens)
        shared = alloc.match_prefix(toks) if self._share else []
        # always leave >= 1 suffix token: prefill must produce logits
        t0 = min(len(shared) * bs, plen - 1)
        s_blk = t0 // bs                # fully-shared blocks mapped
        gather_n = -(-t0 // bs)         # + the partial CoW source
        shared = shared[:gather_n]
        # incref the mapped prefix FIRST so our own allocs below cannot
        # LRU-reclaim it; the CoW source (if any) needs no ref — the
        # gather captures its value before any write lands
        for b in shared[:s_blk]:
            alloc.incref(b)
        owned: List[int] = []
        try:
            for _ in range(total - s_blk):
                owned.append(alloc.alloc())
        except ValueError:
            for b in owned:
                alloc.decref(b)
            for b in shared[:s_blk]:
                alloc.decref(b)
            return False, []
        rows = total * bs
        with self._admit_span(st, rid, plen):
            with TraceAnnotation("serve.prefill"):
                if t0:
                    mini = self._mini_prefix(st.cache,
                                             jnp.asarray(shared, jnp.int32),
                                             rows)
                else:
                    if rows not in st.mini_zeros:
                        st.mini_zeros[rows] = self._place_cache(
                            T.init_cache(self.cfg, 1, rows))
                    mini = st.mini_zeros[rows]
                lg, mini = self._prefill_t0(
                    self.params, mini,
                    jnp.asarray(np.asarray(r.tokens, np.int32)[None]),
                    jnp.zeros((1,), jnp.int32), t0)
                st.admissions += 1
                healthy = self._healthy(lg)
            if not healthy:
                # quarantine BEFORE the pool write and BEFORE registration:
                # a poisoned page must never be published for prefix
                # sharing — and the shared prefix pages this prefill READ
                # are themselves suspect, so evict them from the prefix
                # table too
                for b in owned:
                    alloc.decref(b)
                for b in shared[:s_blk]:
                    alloc.decref(b)
                    alloc.quarantine(b)
                return True, [self._admission_fault(st, rid)]
            with TraceAnnotation("serve.scatter"):
                st.cache = self._write_blocks(st.cache, mini,
                                              jnp.asarray(owned, jnp.int32),
                                              jnp.int32(s_blk))
            chain = shared[:s_blk] + owned
            if self._share:
                alloc.register_prefix(toks, chain)
            st.bt_host[slot, :] = 0
            st.bt_host[slot, :total] = chain
            st.slot_blocks[slot] = chain
            st.hit_tokens += t0
            st.fill_tokens += plen - t0
            st.prompt_tokens += plen
            st.owned_total += len(owned)
            st.shared_total += s_blk
            st.peak_blocks = max(st.peak_blocks, alloc.blocks_in_use())
            return True, self._finish_admission(st, slot, rid, lg, plen, 0,
                                                budget)

    def _finish_admission(self, st: _ServeState, slot: int, rid: int,
                          lg, P: int, s0: int, budget: int) -> List:
        """Shared admission tail: sample the prefill token, then arm the
        slot.  Returns the stream events this admission produced."""
        with TraceAnnotation("serve.first_token"):
            key_r = self._request_key(st.req_key[rid])
            t0 = self._sample(lg, np.asarray([st.req_temp[rid]], np.float32),
                              key_r[None], jnp.zeros((1,), jnp.int32))
            tok = int(np.asarray(t0)[0, 0])
        return self._arm_slot(st, slot, rid, tok, np.asarray(key_r), P, s0,
                              budget)

    def _arm_slot(self, st: _ServeState, slot: int, rid: int, tok: int,
                  key_r, P: int, s0: int, budget: int) -> List:
        """Arm one slot with an ALREADY-sampled first token: set the
        per-slot mirrors, record the token (evicting right away if it
        finishes the request).  Solo admission samples then calls this;
        packed admission samples its whole pack in one vectorized call
        and arms per segment."""
        st.pos[slot], st.start[slot] = P, s0
        st.temps[slot], st.eos[slot] = st.req_temp[rid], st.req_eos[rid]
        st.keys[slot], st.steps[slot] = key_r, 1
        st.cur[slot] = tok
        st.sched.admit(slot, rid, budget)
        now = self._now_ms()
        st.t_admit.setdefault(rid, now)
        st.ttft[rid] = now - st.t_submit.get(rid, now)
        st.last_tok_ms[slot] = now
        events: List = [TokenEvent(rid, tok)]
        if st.sched.record_one(slot, tok, st.req_eos[rid]):
            reason = (FinishReason.EOS if tok == st.req_eos[rid]
                      else FinishReason.MAX_NEW)
            events.append(FinishEvent(rid, self._evict(st, slot, rid, reason,
                                                       "", now)))
        return events

    # ----------------------------------------------------- packed admission

    def _pack_key(self, st: _ServeState, rid: int) -> Optional[int]:
        """Packing-bin key (the segment width) for a queued request, or
        None when it must use solo admission: a prompt whose power-of-two
        bucket fell back to the exact length (``_bucket``'s max_seq clamp
        or the dense budget clamp) has per-length geometry no shared
        executable covers.  Paged segments additionally round up to the
        block size so every segment scatters a whole number of blocks."""
        if self._paged:
            plen, _, _ = st.plans[rid]
            P = _bucket(plen, self.sc.max_seq)
            if P & (P - 1):
                return None
            return max(P, self.sc.block_size)
        P, _, _ = st.plans[rid]
        return None if P & (P - 1) else P

    def _packed_zero(self, st: _ServeState, batch: int, rows: int):
        """Zero mini-cache template for one pack bin (prefill is pure, so
        each bin shape's template is built once per session and reused)."""
        key = (batch, rows)
        if key not in st.packed_zeros:
            st.packed_zeros[key] = self._place_cache(
                T.init_cache(self.cfg, batch, rows))
        return st.packed_zeros[key]

    def _admit_packed_sweep(self, st: _ServeState) -> List:
        """One packed admission sweep: plan packs over the queue head (at
        most one entry per free slot) and admit each through the packed
        executables.  Unpackable entries and anything past a paged pool
        starvation stay queued for the solo loop / a later sweep."""
        free = st.sched.free_slots()
        n = min(len(free), len(st.queue))
        if n == 0:
            return []
        head = [(rid, self._pack_key(st, rid))
                for rid in itertools.islice(st.queue, n)]
        packs, _ = Scheduler.plan_packs(head)
        events: List = []
        admitted: set = set()
        si = 0                       # next free slot to hand a pack
        for P, rids in packs:
            slots = [int(s) for s in free[si:si + len(rids)]]
            if self._paged:
                plans = self._alloc_packed_paged(st, rids)
                done = [p[0] for p in plans]
                if not done:
                    break            # pool starvation: defer the rest
            else:
                done = rids
            with self._pack_span(st, done, P):
                if self._paged:
                    evs = self._admit_packed_paged(st, slots, plans, P)
                elif self.cfg.family == "dense":
                    evs = self._admit_packed_dense(st, slots, rids, P)
                else:
                    evs = self._admit_packed_batch(st, slots, rids, P)
            admitted.update(done)
            si += len(done)
            events.extend(evs)
            if len(done) < len(rids):
                break                # pool starvation: defer the rest
        if admitted:
            st.queue = collections.deque(
                r for r in st.queue if r not in admitted)
        return events

    def _pack_span(self, st: _ServeState, rids: List[int], P: int):
        """The ``serve.admit_pack`` span of one packed admission: the solo
        span's args summed over the pack (``bucket``: the rows of its
        power-of-two segment count, dummies included), its first ``rid``
        and its real ``segments``.  Counts the prefill rows."""
        now = self._now_ms()
        rows = _pow2_ceil(len(rids)) * P
        st.prefill_rows += rows
        return TraceAnnotation(
            "serve.admit_pack", rid=rids[0], segments=len(rids),
            prompt=sum(len(st.reqs[r].tokens) for r in rids), bucket=rows,
            wait_ms=sum(now - st.t_submit[r] for r in rids))

    def _admit_packed_dense(self, st: _ServeState, slots: List[int],
                            rids: List[int], P: int) -> List:
        """Dense-family packed admission (dense layout): left-padded
        segments concatenated into ONE (1, N*P) sequence, block-diagonal
        attention via segment ids, one fused per-slot scatter.  Dummy
        segments (pow2 rounding) come FIRST and write the first real
        slot, which its real segment overwrites (later write wins)."""
        n_real = len(rids)
        N = _pow2_ceil(n_real)
        nd = N - n_real
        L = N * P
        toks = np.zeros((1, L), np.int32)
        segs = np.full((1, L), -1, np.int32)
        pos = np.zeros((1, L), np.int32)
        last = np.zeros(N, np.int32)
        slot_vec = np.full(N, slots[0], np.int32)
        for i in range(N):
            pos[0, i * P:(i + 1) * P] = np.arange(P, dtype=np.int32)
            last[i] = (i + 1) * P - 1
        for j, rid in enumerate(rids):
            i = nd + j
            r = st.reqs[rid]
            s0 = P - len(r.tokens)
            off = i * P
            toks[0, off + s0:off + P] = r.tokens
            segs[0, off + s0:off + P] = i
            pos[0, off:off + P] -= s0
            slot_vec[i] = slots[j]
        tmpl = self._packed_zero(st, 1, L)
        lg, mini = self._prefill_packed(
            self.params, tmpl, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(segs), jnp.asarray(last), P)
        # scatter-then-quarantine: a segment that fails the health probe
        # below leaves poisoned rows in a slot that stays FREE — batch
        # rows are independent, inactive slots' health bits are ignored,
        # and the next admission's scatter fully overwrites the slot
        # (solo admission instead skips the scatter; same observable
        # tokens either way)
        st.cache = self._write_slot_segments(st.cache, mini,
                                             jnp.asarray(slot_vec), P)
        return self._finish_pack(st, slots, rids, lg, nd,
                                 [st.plans[rid][0] for rid in rids],
                                 [st.plans[rid][1] for rid in rids])

    def _admit_packed_batch(self, st: _ServeState, slots: List[int],
                            rids: List[int], P: int) -> List:
        """Scanned-family packed admission (dense layout): one (N, P)
        left-padded batch through the batch-capable solo prefill (MoE's
        per-token expert capacity keeps ragged batching exact), one fused
        row-per-slot scatter.  Dummy rows are all-zero pseudo-prompts at
        start 0 — batch invariance keeps them from touching real rows."""
        sc = self.sc
        n_real = len(rids)
        N = _pow2_ceil(n_real)
        nd = N - n_real
        toks = np.zeros((N, P), np.int32)
        starts = np.zeros(N, np.int32)
        slot_vec = np.full(N, slots[0], np.int32)
        for j, rid in enumerate(rids):
            i = nd + j
            r = st.reqs[rid]
            s0 = P - len(r.tokens)
            toks[i, s0:] = r.tokens
            starts[i] = s0
            slot_vec[i] = slots[j]
        tmpl = self._packed_zero(st, N, sc.max_seq)
        lg, mini = self._prefill(self.params, tmpl, jnp.asarray(toks),
                                 jnp.asarray(starts))
        st.cache = self._write_slots(st.cache, mini,
                                     jnp.asarray(slot_vec, jnp.int32))
        return self._finish_pack(st, slots, rids, lg, nd,
                                 [st.plans[rid][0] for rid in rids],
                                 [st.plans[rid][1] for rid in rids])

    def _alloc_packed_paged(self, st: _ServeState, rids: List[int]) -> List:
        """Block plans of a paged pack: walks it FIFO mapping shared
        prefix blocks and allocating owned ones per request, stopping at
        the first the pool cannot satisfy (it and everything behind it
        stay queued — solo deferral semantics).  Returns ``[(rid, plen,
        shared_mapped, owned, t0, total)]`` for the requests it covers."""
        alloc = st.alloc
        bs = self.sc.block_size
        plans = []
        for rid in rids:
            plen, _, _ = st.plans[rid]
            toks_t = tuple(int(t) for t in st.reqs[rid].tokens)
            shared = alloc.match_prefix(toks_t) if self._share else []
            t0 = min(len(shared) * bs, plen - 1)
            s_blk = t0 // bs
            total = -(-plen // bs)
            for b in shared[:s_blk]:
                alloc.incref(b)
            owned: List[int] = []
            try:
                for _ in range(total - s_blk):
                    owned.append(alloc.alloc())
            except ValueError:
                for b in owned:
                    alloc.decref(b)
                for b in shared[:s_blk]:
                    alloc.decref(b)
                break
            plans.append((rid, plen, shared[:s_blk], owned, t0, total))
        return plans

    def _admit_packed_paged(self, st: _ServeState, slots: List[int],
                            plans: List, W: int) -> List:
        """Paged packed admission of the requests :meth:`_alloc_packed_paged`
        found blocks for.

        Segments are RIGHT-padded to the block-aligned
        width ``W`` at start 0 (the sharing contract) and FULLY
        recomputed (t0=0: intra-pack gathering would need the pack's own
        pages before they are written; full recompute is bit-identical
        by the suffix-prefill contract), then scattered block-wise in
        one call — shared prefix blocks are mapped, never rewritten
        (first-writer-wins), and a faulted segment's rows go to the
        block-0 sink.  Prefix registration happens per segment AFTER the
        health check, exactly as solo."""
        sc = self.sc
        alloc = st.alloc
        bs = sc.block_size
        n_real = len(plans)
        N = _pow2_ceil(n_real)
        nd = N - n_real
        if self.cfg.family == "dense":
            L = N * W
            toks = np.zeros((1, L), np.int32)
            segs = np.full((1, L), -1, np.int32)
            pos = np.zeros((1, L), np.int32)
            last = np.zeros(N, np.int32)
            for i in range(N):
                pos[0, i * W:(i + 1) * W] = np.arange(W, dtype=np.int32)
                last[i] = (i + 1) * W - 1
            for j, (rid, plen, _, _, _, _) in enumerate(plans):
                i = nd + j
                off = i * W
                toks[0, off:off + plen] = st.reqs[rid].tokens
                segs[0, off:off + plen] = i
                last[i] = off + plen - 1
            tmpl = self._packed_zero(st, 1, L)
            lg, mini = self._prefill_packed(
                self.params, tmpl, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(segs), jnp.asarray(last), W)
        else:
            toks = np.zeros((N, W), np.int32)
            last = np.zeros(N, np.int32)
            for j, (rid, plen, _, _, _, _) in enumerate(plans):
                i = nd + j
                toks[i, :plen] = st.reqs[rid].tokens
                last[i] = plen - 1
            tmpl = self._packed_zero(st, N, W)
            lg, mini = self._prefill_ragged(
                self.params, tmpl, jnp.asarray(toks),
                jnp.zeros(N, jnp.int32), jnp.asarray(last))
        healthy = np.asarray(self._health(lg)).astype(bool)
        bids = np.zeros((N, W // bs), np.int32)       # default: sink 0
        for j, (rid, plen, shared_m, owned, t0, total) in enumerate(plans):
            if sc.health_checks and not healthy[nd + j]:
                continue
            bids[nd + j, len(shared_m):total] = owned
        st.cache = self._scatter_segments(st.cache, mini,
                                          jnp.asarray(bids), W)
        toks_s, keys = self._sample_pack(st, [p[0] for p in plans], nd, N,
                                         lg)
        st.packed_packs += 1
        st.packed_segments += n_real
        st.packed_dummies += nd
        events: List = []
        for j, (rid, plen, shared_m, owned, t0, total) in enumerate(plans):
            st.admissions += 1
            if sc.health_checks and not healthy[nd + j]:
                for b in owned:
                    alloc.decref(b)
                for b in shared_m:
                    alloc.decref(b)
                    alloc.quarantine(b)
                events.append(self._admission_fault(st, rid))
                continue
            chain = shared_m + owned
            if self._share:
                alloc.register_prefix(
                    tuple(int(t) for t in st.reqs[rid].tokens), chain)
            slot = slots[j]
            st.bt_host[slot, :] = 0
            st.bt_host[slot, :total] = chain
            st.slot_blocks[slot] = chain
            st.hit_tokens += t0
            st.fill_tokens += plen - t0
            st.prompt_tokens += plen
            st.owned_total += len(owned)
            st.shared_total += len(shared_m)
            st.peak_blocks = max(st.peak_blocks, alloc.blocks_in_use())
            events.extend(self._arm_slot(st, slot, rid, int(toks_s[nd + j, 0]),
                                         keys[nd + j], plen, 0,
                                         st.plans[rid][2]))
        return events

    def _sample_pack(self, st: _ServeState, rids: List[int], nd: int,
                     N: int, lg):
        """ONE vectorized first-token sample for a whole pack: row i uses
        request i's own key/temperature, so each row's token is exactly
        what solo admission's (1,)-shaped sample would draw (dummy rows
        sample greedy garbage that nothing reads)."""
        temps = np.zeros(N, np.float32)
        keys = np.zeros((N, 2), np.uint32)
        for j, rid in enumerate(rids):
            temps[nd + j] = st.req_temp[rid]
            keys[nd + j] = np.asarray(self._request_key(st.req_key[rid]))
        toks = np.asarray(self._sample(lg, temps, jnp.array(keys),
                                       jnp.zeros(N, jnp.int32)))
        return toks, keys

    def _finish_pack(self, st: _ServeState, slots: List[int],
                     rids: List[int], lg, nd: int, Ps: List[int],
                     s0s: List[int]) -> List:
        """Dense-layout packed admission tail: per-segment health probe,
        one vectorized sample, per-segment arming in FIFO order."""
        sc = self.sc
        N = nd + len(rids)
        healthy = np.asarray(self._health(lg)).astype(bool)
        toks, keys = self._sample_pack(st, rids, nd, N, lg)
        st.packed_packs += 1
        st.packed_segments += len(rids)
        st.packed_dummies += nd
        events: List = []
        for j, rid in enumerate(rids):
            i = nd + j
            st.admissions += 1
            if sc.health_checks and not healthy[i]:
                events.append(self._admission_fault(st, rid))
                continue
            st.prompt_tokens += len(st.reqs[rid].tokens)
            events.extend(self._arm_slot(st, slots[j], rid,
                                         int(toks[i, 0]), keys[i],
                                         Ps[j], s0s[j],
                                         st.plans[rid][2]))
        return events

    def warmup(self, prompt_lens: Optional[Sequence[int]] = None,
               max_new: int = 2,
               temperature: Optional[float] = None) -> Dict[str, int]:
        """AOT-compile the serving executables by driving synthetic
        traffic through every admission bin, so steady-state serving
        never retraces.

        For each prompt-length bucket (default: every power-of-two
        bucket that fits ``max_seq``) and one representative real pack
        size per power-of-two pack bin, a synthetic batch is served to
        completion — populating the jit caches with REAL calls (a bare
        ``jit.lower().compile()`` would not populate the call cache)
        for packed + solo prefill, the cache scatters, the decode step,
        the samplers (greedy always; the categorical sampler too when
        ``temperature`` is given) and the health probe.  The warmup
        sessions are discarded: ``last_serve_stats``/``last_results``
        are restored and the next ``submit()`` starts fresh.  Returns
        the compiled-executable census (:meth:`executable_counts`)."""
        sc = self.sc
        if self._st is not None and not self._st.drained:
            raise ValueError("warmup() requires an idle engine")
        if prompt_lens is None:
            prompt_lens, p = [], 8
            while p + 1 <= sc.max_seq:
                prompt_lens.append(p)
                p *= 2
        plens = sorted({_bucket(int(n), sc.max_seq) for n in prompt_lens})
        # one representative real count per pow2 pack bin (a real count r
        # packs as _pow2_ceil(r), dummies filling the difference)
        bins = sorted({_pow2_ceil(r) for r in range(1, sc.max_batch + 1)})
        sizes = [min(N, sc.max_batch) for N in bins]
        saved = (self.last_serve_stats, self.last_results, dict(self._ended))
        temp = 0.0 if temperature is None else float(temperature)
        for P in plens:
            plen = max(1, P - 1)      # lands in bucket P
            # keep the plan at bucket P: a budget the bucket's pad rows
            # would eat forces the exact-length fallback signature
            mn = max(1, min(max_new, sc.max_seq - P))
            for r in sizes:
                reqs = [Request(np.ones(plen, np.int32), max_new=mn,
                                temperature=temp, seed=0)
                        for _ in range(r)]
                self.serve(reqs)
        self._st = None
        self.last_serve_stats, self.last_results, self._ended = saved
        return self.executable_counts()

    def _release_blocks(self, st: _ServeState, slot: int,
                        quarantine: bool = False) -> None:
        """Eviction-side block bookkeeping: drop this slot's refs (a
        registered prefix block parks in the allocator's LRU cache at
        refcount 0, an unregistered one frees) and zero its table row
        so the parked slot writes the block-0 sink.  ``quarantine=True``
        (FAULT eviction) additionally unregisters the slot's now-unmapped
        registered blocks — possibly-poisoned pages must not be matched by
        future prefix lookups."""
        blocks = st.slot_blocks[slot]
        for b in blocks:
            st.alloc.decref(b)
        if quarantine:
            for b in blocks:
                st.alloc.quarantine(b)
        st.slot_blocks[slot] = []
        st.bt_host[slot, :] = 0

    def _evict(self, st: _ServeState, slot: int, rid: int,
               reason: FinishReason, detail: str, now: float) -> ServeResult:
        """Common slot teardown for every non-admission finish path."""
        out = st.sched.evict(slot)
        if self._paged:
            self._release_blocks(st, slot,
                                 quarantine=reason is FinishReason.FAULT)
        # a parked sampled slot would otherwise disable the all-greedy
        # sampler shortcut for the rest of the stream
        st.temps[slot] = 0.0
        # park at row 0: admission rewrites the slot's rows before it is
        # armed again, and the decode kernel scans one tile meanwhile
        st.pos[slot] = st.start[slot] = 0
        return self._finish(st, rid, out, reason, detail, now)

    # --------------------------------------------------------- the serve loop

    def serve_stream(self, strict: Optional[bool] = None):
        """Drive the live session to completion, yielding
        :class:`TokenEvent`/:class:`FinishEvent` as they happen.

        One consumer at a time: the generator mutates the engine's session
        state, so interleaving two ``serve_stream`` iterators is undefined.
        New :meth:`submit` calls made BETWEEN iterations (e.g. from the
        consuming loop's body) are admitted into freed slots — the loop
        runs until queue, slots, and pending events are all drained, then
        finalizes ``self.last_serve_stats``.

        Every event passes through the session's ``pending`` buffer and is
        only yielded at a consistent STEP BOUNDARY (all bookkeeping for the
        step — records, evictions, block releases — already applied).  A
        consumer may therefore abandon the generator at any yield and
        :meth:`snapshot` right there: events it never consumed are still
        in the buffer and are re-delivered by the restored engine's
        stream.
        """
        sc = self.sc
        strict = sc.strict if strict is None else strict
        st = self._st
        if st is None:
            return
        emit = st.pending.append
        while not st.drained:
            # submit-time events (sheds) first, in submission order
            while st.pending:
                yield st.pending.pop(0)
            if st.drained:
                break
            # queue-wait expiry: a queued request past its deadline (or the
            # global queue-wait cap) finishes DEADLINE without a slot
            if st.queue:
                now = self._now_ms()
                kept: collections.deque = collections.deque()
                while st.queue:
                    rid = st.queue.popleft()
                    lim = self._queue_limit(st, rid)
                    if lim is not None and now - st.t_submit[rid] > lim:
                        st.deadline_evictions += 1
                        res = self._finish(st, rid, np.zeros(0, np.int32),
                                           FinishReason.DEADLINE,
                                           "expired while queued", now)
                        emit(FinishEvent(rid, res))
                    else:
                        kept.append(rid)
                st.queue = kept
            # packed admission first: the queue head (one entry per free
            # slot) is grouped into (bucket, count) bins and served from
            # the shared pack executables; unpackable entries fall
            # through to the solo loop below (see module docstring)
            if self._packed and st.queue:
                for ev in self._admit_packed_sweep(st):
                    emit(ev)
            # admission into freed slots (FIFO; paged may defer on pool
            # starvation until an eviction frees blocks)
            for slot in st.sched.free_slots():
                if not st.queue:
                    break
                if self._paged:
                    ok, events = self._admit_paged(st, int(slot),
                                                   st.queue[0])
                    if not ok:
                        if not st.sched.any_active:
                            rid = st.queue.popleft()
                            msg = (f"request {rid} needs more KV blocks "
                                   f"than the pool can ever free "
                                   f"(num_blocks={self._num_blocks}); "
                                   "raise ServeConfig.num_blocks")
                            if strict:
                                raise ValueError(msg)
                            st.shed += 1
                            res = self._finish(st, rid,
                                               np.zeros(0, np.int32),
                                               FinishReason.SHED, msg,
                                               self._now_ms())
                            emit(FinishEvent(rid, res))
                            continue
                        break
                    st.queue.popleft()
                else:
                    events = self._admit_dense(st, int(slot),
                                               st.queue.popleft())
                for ev in events:
                    emit(ev)
            # admission boundary: a consistent point to hand events out
            while st.pending:
                yield st.pending.pop(0)
            if not st.sched.any_active:
                continue    # admitted requests may finish at token 0
            self._step(st)
            # the step's bookkeeping is fully applied — NOW hand events out
            # (tokens before finishes; snapshot() is safe at every yield)
            while st.pending:
                yield st.pending.pop(0)
        self._finalize_stats(st)

    def _step(self, st: _ServeState) -> None:
        """One decode step over all slots, its events left in
        ``st.pending``; the ``serve.step`` span (``step``, ``active``
        slots, ``live_rows``, ``read_rows`` and ``scan_rows`` of KV, all
        read from host mirrors) and its ``serve.dispatch``, ``serve.sync``
        and ``serve.record`` phases."""
        sc = self.sc
        act = st.sched.active
        n_act = int(act.sum())
        live = int((st.pos[act] - st.start[act]).sum()) + n_act
        read = sc.max_batch * sc.max_seq
        scan = (int(decode_rows_scanned(st.pos, st.start, sc.max_seq).sum())
                if self._tile_scan else read)
        st.decode_steps += 1
        st.active_slot_steps += n_act
        st.kv_rows_live += live
        st.kv_rows_read += read
        st.kv_rows_scanned += scan
        with TraceAnnotation("serve.step", step=st.decode_steps,
                             active=n_act, live_rows=live, read_rows=read,
                             scan_rows=scan):
            if self._paged:
                # grow each active slot's table before the row it is about
                # to write crosses into an unmapped block
                for slot in np.flatnonzero(act):
                    need = int(st.pos[slot]) // sc.block_size
                    if need >= len(st.slot_blocks[slot]):
                        b = st.alloc.alloc()  # pool sized: never fails here
                        st.slot_blocks[slot].append(b)
                        st.bt_host[slot, need] = b
                        st.peak_blocks = max(st.peak_blocks,
                                             st.alloc.blocks_in_use())
            # ONE decode step for ALL slots at their own positions + ONE
            # vectorized sample; a single (B, 2) transfer back per step
            # carrying [token, healthy] per slot.
            # jnp.array COPIES each host mirror at hand-off: jnp.asarray
            # would zero-copy alias the numpy buffers on CPU, racing the
            # async dispatch against the in-place updates below.  Each
            # copy is an eager ``jit_convert_element_type`` execution.
            with TraceAnnotation("serve.dispatch"):
                if self._paged:
                    lg, st.cache, health = self._decode_paged(
                        self.params, st.cache, jnp.array(st.bt_host),
                        jnp.array(st.cur), jnp.array(st.pos),
                        jnp.array(st.start))
                else:
                    lg, st.cache, health = self._decode(
                        self.params, st.cache, jnp.array(st.cur),
                        jnp.array(st.pos), jnp.array(st.start))
                packed = self._sample_packed(lg, health, st.temps,
                                             jnp.array(st.keys),
                                             jnp.array(st.steps))
            # sync BEFORE mutating the pos/steps mirrors: under async
            # dispatch the jnp.array host->device copies above may still
            # be pending, and an in-place bump here would let the
            # in-flight step read the NEXT step's values (a real race —
            # it fired on the categorical sampler's ``steps`` input)
            with TraceAnnotation("serve.sync"):
                arr = np.asarray(packed)
            with TraceAnnotation("serve.record"):
                self._record(st, arr)

    def _record(self, st: _ServeState, arr: np.ndarray) -> None:
        """A decode step's bookkeeping from its (B, 2) ``[token, healthy]``
        readback: quarantine, record, evictions, the deadline sweep, and
        the step's events into ``st.pending``."""
        sc = self.sc
        emit = st.pending.append
        # only the slots that decoded move on: a parked slot stays at row
        # 0, so the flash kernel scans one tile for it
        np.minimum(st.pos + st.sched.active, sc.max_seq - 1, out=st.pos)
        st.steps += 1
        tok_h = arr[:, 0].astype(np.int32)
        healthy = arr[:, 1].astype(bool)
        st.cur = tok_h[:, None].copy()
        now = self._now_ms()

        # NaR / non-finite quarantine — BEFORE record(), so the faulted
        # slot's garbage token never lands in its output.  Other slots
        # are untouched: the model is batch-composition invariant, so
        # their logits (and tokens) are bit-identical to a clean run.
        if sc.health_checks:
            for slot in np.flatnonzero(st.sched.active & ~healthy):
                rid = int(st.sched.slot_req[slot])
                st.faults += 1
                res = self._evict(st, int(slot), rid, FinishReason.FAULT,
                                  "non-finite logits quarantined "
                                  "mid-decode", now)
                emit(FinishEvent(rid, res))

        act = np.flatnonzero(st.sched.active)
        finished = st.sched.record(tok_h, st.eos)
        token_events = []
        for slot in act:
            rid = int(st.sched.slot_req[slot])
            if st.last_tok_ms[slot] > 0:
                st.token_lats.append(now - st.last_tok_ms[slot])
            st.last_tok_ms[slot] = now
            token_events.append(TokenEvent(rid, int(tok_h[slot])))
        finish_events = []
        for slot in finished:
            rid = int(st.sched.slot_req[slot])
            reason = (FinishReason.EOS if tok_h[slot] == st.eos[slot]
                      else FinishReason.MAX_NEW)
            res = self._evict(st, int(slot), rid, reason, "", now)
            finish_events.append(FinishEvent(rid, res))
        # in-flight deadline sweep (after record: the step's token is
        # part of the partial output)
        for slot in np.flatnonzero(st.sched.active):
            rid = int(st.sched.slot_req[slot])
            dl = st.reqs[rid].deadline_ms
            if dl is not None and now - st.t_submit[rid] > dl:
                st.deadline_evictions += 1
                res = self._evict(st, int(slot), rid,
                                  FinishReason.DEADLINE,
                                  "deadline exceeded mid-decode", now)
                finish_events.append(FinishEvent(rid, res))
        for ev in token_events + finish_events:
            emit(ev)

    def _finalize_stats(self, st: _ServeState) -> None:
        # measured scheduler counters (e.g. the decode-throughput benchmark
        # reports real slot utilization from these, not an estimate)
        sc = self.sc
        stats = {
            "decode_steps": st.decode_steps,
            "slot_steps": st.decode_steps * sc.max_batch,
            "active_slot_steps": st.active_slot_steps,
            "admissions": st.admissions,
            "kv_layout": "paged" if self._paged else "dense",
            "requests": len(st.reqs),
            "faults": st.faults,
            "deadline_evictions": st.deadline_evictions,
            "shed": st.shed,
            "finish_reasons": collections.Counter(
                r.finish.value for r in st.results.values()),
            "ttft_ms": list(st.ttft.values()),
            "token_latency_ms": list(st.token_lats),
            "packed_prefill": self._packed,
            "packed_packs": st.packed_packs,
            "packed_segments": st.packed_segments,
            "packed_dummies": st.packed_dummies,
            **{k: getattr(st, k) for k in COUNTERS},
        }
        if self._paged:
            stats.update({
                "block_size": sc.block_size,
                "pool_blocks": self._num_blocks - 1,
                "peak_blocks_in_use": st.peak_blocks,
                "prefill_tokens": st.fill_tokens,
                "prefix_hit_tokens": st.hit_tokens,
                "prefix_hit_rate": st.hit_tokens / max(st.prompt_tokens, 1),
                "owned_blocks": st.owned_total,
                "shared_blocks": st.shared_total,
                "prefix_lookups": st.alloc.lookups,
                "prefix_matches": st.alloc.hits,
            })
        self.last_serve_stats = stats
        self._close(st)

    # -------------------------------------------------- snapshot / restore

    def snapshot(self) -> dict:
        """Capture the live serve session as one picklable dict.

        Includes every byte the remaining stream depends on: the device
        cache leaves (``jax.device_get``), scheduler + allocator state,
        per-slot host mirrors, and per-request bookkeeping.  Deadline
        timestamps are stored as ELAPSED ms so :meth:`restore` rebases
        them onto the restoring engine's clock (downtime doesn't count
        against a deadline).  Restore on an engine built from the same
        ``ModelConfig`` + params + ``ServeConfig`` completes the stream
        with bit-identical tokens (see the module docstring contract).
        """
        st = self._st
        if st is None:
            raise ValueError("no serve session to snapshot")
        sc = self.sc
        now = self._now_ms()
        sched = st.sched
        snap = {
            "version": 1,
            "kv_layout": "paged" if self._paged else "dense",
            # informational only: the packed-admission invariance contract
            # means a snapshot restores bit-identically onto an engine
            # with EITHER packed_prefill setting (still-queued requests
            # are admitted by the restoring engine's own path)
            "packed_prefill": self._packed,
            "max_batch": sc.max_batch,
            "max_seq": sc.max_seq,
            "reqs": [dataclasses.replace(
                         r, tokens=np.array(r.tokens, np.int32))
                     for r in st.reqs],
            "plans": list(st.plans),
            "req_temp": list(st.req_temp),
            "req_eos": list(st.req_eos),
            "req_key": list(st.req_key),
            "queue": list(st.queue),
            "pending": list(st.pending),
            "results": dict(st.results),
            "submit_elapsed_ms": {r: now - t for r, t in st.t_submit.items()},
            "admit_elapsed_ms": {r: now - t for r, t in st.t_admit.items()},
            "ttft": dict(st.ttft),
            "sched": {
                "active": sched.active.copy(),
                "slot_req": sched.slot_req.copy(),
                "out_buf": sched.out_buf.copy(),
                "out_len": sched.out_len.copy(),
                "budget": sched.budget.copy(),
            },
            "mirrors": {k: getattr(st, k).copy()
                        for k in ("pos", "start", "cur", "temps", "eos",
                                  "keys", "steps")},
            "last_tok_elapsed_ms": np.where(
                st.last_tok_ms > 0, now - st.last_tok_ms, 0.0),
            "counters": {k: getattr(st, k)
                         for k in ("decode_steps", "active_slot_steps",
                                   "admissions", "faults",
                                   "deadline_evictions", "shed",
                                   "hit_tokens", "fill_tokens",
                                   "prompt_tokens", "owned_total",
                                   "shared_total", "peak_blocks",
                                   "packed_packs", "packed_segments",
                                   "packed_dummies", "prefill_rows",
                                   "kv_rows_live", "kv_rows_read",
                                   "kv_rows_scanned")},
            "token_lats": list(st.token_lats),
            "cache": jax.device_get(st.cache),
        }
        if self._paged:
            a = st.alloc
            snap["bt_host"] = st.bt_host.copy()
            snap["slot_blocks"] = [list(b) for b in st.slot_blocks]
            snap["alloc"] = {
                "refcount": a.refcount.copy(),
                "free": list(a.free),
                "cached": list(a.cached.keys()),
                "table": {h: list(v) for h, v in a.table.items()},
                "owner": dict(a.owner),
                "hits": a.hits,
                "lookups": a.lookups,
            }
        return snap

    def restore(self, snap: dict) -> None:
        """Rebuild a serve session from :meth:`snapshot` (see there).  The
        engine must have been constructed with the same ``ModelConfig``,
        params, and ``ServeConfig`` as the snapshotting one — that
        compatibility is the caller's contract (layout mismatches are
        rejected; weight mismatches cannot be detected cheaply)."""
        sc = self.sc
        want = "paged" if self._paged else "dense"
        if snap.get("kv_layout") != want or \
                snap.get("max_batch") != sc.max_batch or \
                snap.get("max_seq") != sc.max_seq:
            raise ValueError(
                f"snapshot layout ({snap.get('kv_layout')}, "
                f"max_batch={snap.get('max_batch')}, "
                f"max_seq={snap.get('max_seq')}) does not match this "
                f"engine ({want}, max_batch={sc.max_batch}, "
                f"max_seq={sc.max_seq})")
        st = _ServeState(self, init_cache=False)
        now = self._now_ms()
        st.reqs = list(snap["reqs"])
        st.plans = list(snap["plans"])
        st.req_temp = list(snap["req_temp"])
        st.req_eos = list(snap["req_eos"])
        st.req_key = list(snap["req_key"])
        st.queue = collections.deque(snap["queue"])
        st.pending = list(snap["pending"])
        st.results = dict(snap["results"])
        st.t_submit = {r: now - e
                       for r, e in snap["submit_elapsed_ms"].items()}
        st.t_admit = {r: now - e
                      for r, e in snap["admit_elapsed_ms"].items()}
        st.ttft = dict(snap["ttft"])
        sd = snap["sched"]
        sch = Scheduler(sc.max_batch, sd["out_buf"].shape[1])
        sch.active = sd["active"].copy()
        sch.slot_req = sd["slot_req"].copy()
        sch.out_buf = sd["out_buf"].copy()
        sch.out_len = sd["out_len"].copy()
        sch.budget = sd["budget"].copy()
        st.sched = sch
        for k, v in snap["mirrors"].items():
            setattr(st, k, v.copy())
        el = np.asarray(snap["last_tok_elapsed_ms"], np.float64)
        st.last_tok_ms = np.where(el > 0, now - el, 0.0)
        for k, v in snap["counters"].items():
            setattr(st, k, v)
        st.token_lats = list(snap["token_lats"])
        # jnp.array COPIES the host leaves: the donated decode step may not
        # alias a buffer the snapshot dict still references
        st.cache = self._place_cache(jax.tree.map(jnp.array, snap["cache"]))
        if self._paged:
            st.bt_host = snap["bt_host"].copy()
            st.slot_blocks = [list(b) for b in snap["slot_blocks"]]
            a = BlockAllocator(self._num_blocks, sc.block_size)
            sa = snap["alloc"]
            a.refcount = sa["refcount"].copy()
            a.free = collections.deque(sa["free"])
            a.cached = collections.OrderedDict(
                (b, None) for b in sa["cached"])
            a.table = {h: list(v) for h, v in sa["table"].items()}
            a.owner = dict(sa["owner"])
            a.hits, a.lookups = sa["hits"], sa["lookups"]
            st.alloc = a
        # the replaced session's counts stay in counters(); the restored
        # one brings the counts it was snapshotted with
        if self._st is not None:
            self._close(self._st)
        self._st = st
