"""Pallas TPU kernels: flash attention with a posit SRT-divided normalizer,
forward AND backward.

Forward: one ``pallas_call`` per attention — each grid step owns one
(batch*head, q-tile) pair and scans the KV sequence in chunks with the
online-softmax running statistics ``(m, l, acc)`` carried in-register — the
standard flash pattern, so no ``(Sq, Sk)`` score tensor and no broadcast
denominator ever materialize in HBM.  The final ``o = acc / l`` normalizer
runs through the in-kernel digit-recurrence datapath
(:func:`repro.kernels.posit_div.divide_floats_block`, so any planned format
including posit64 works) as a rowwise posit division: ``l`` is
quantized/decoded once per query row (a ``(bq, 1)`` column), exactly like
the dedicated rowwise divider kernel.  Fully-masked rows (l == 0) divide by
the format's minpos instead (see :func:`_minpos_eps`) and come out 0.

GQA is handled by the BlockSpec index map: the KV block index is derived
from the query-head index (``h // G``), so grouped K/V are never repeated
in memory.  Three optional per-sequence (B,) int32 inputs make the kernel
serve slot-based continuous batching, where every batch row can sit at a
different sequence offset inside ONE compiled kernel:

Packed multi-prompt prefill additionally rides two optional PER-POSITION
int32 inputs, ``seg_q`` (B, Sq) and ``seg_kv`` (B, Sk): when given, score
entries whose query and key segment ids differ are masked (exact zeros in
the online-softmax recurrence), which makes causal attention over a
concatenation of N prompts block-diagonal — one kernel launch prefills N
admission prompts at once.  Pad positions carry segment id -1 in BOTH
arrays, so the segment mask subsumes the per-segment pad masking
``kv_start`` provides in the solo layout.  This is a masking change
riding the existing per-sequence scalar plumbing, not a new kernel: the
(m, l, acc) recurrence, tile geometry, and SRT normalizer are untouched.

  * ``kv_start`` masks a per-sequence pad PREFIX (``k_pos < kv_start[b]``
    is masked) — the engine's chunked ragged prefill uses this so
    left-padded short prompts never attend pad positions.
  * ``kv_len`` masks a per-sequence valid SUFFIX (``k_pos >= kv_len[b]``
    is masked) — per-slot KV-cache lengths, so a decode step over a full
    ``max_seq`` cache only attends each slot's written rows.
  * ``q_pos`` offsets each sequence's query positions for the causal /
    window masks (added to the static ``q_offset``) — per-slot decode
    positions, so slots at heterogeneous offsets share one kernel launch.

Live tiles only: each program scans just the KV tiles ``[lo, hi)`` that
can hold a key one of its queries attends (:func:`kv_tile_bounds`, from
``kv_start``, ``kv_len``, ``q_pos`` and the causal / window masks), not
all of ``max_seq``; a decode step then costs each slot about its own
length.  A tile whose every key is masked is an exact no-op of the
``(m, l, acc)`` recurrence (``m`` stays, ``corr = exp(0) = 1``, ``p =
0``), so skipping it leaves every output and every saved residual bit
for bit as the full scan gave them.  Value rows outside ``[kv_start,
kv_len)`` in a scanned tile are zeroed, so a NaN there cannot reach
``acc`` through ``0 * NaN``.  The engine counts the rows it scans with
the same rule (:func:`decode_rows_scanned`).

Backward (recompute style, the flash-attention backward): the forward
additionally saves per-row residuals ``(m, l)`` — the online-softmax row
max and row sum, i.e. the logsumexp in factored form ``lse = m + log l`` —
at O(B*H*Sq) memory, never O(Sq*Sk).  Two kernels then recompute score
tiles blockwise:

  * ``dq`` kernel — grid over (batch*head, q-tile), scans KV tiles:
    ``s = q k^T``, ``p = (exp(s - m)) / l``, ``dp = dO v^T``,
    ``ds = p * (dp - D)``, ``dq += ds k``.
  * ``dk/dv`` kernel — grid over (batch*kv-head, kv-tile), scans the G
    grouped query heads and q-tiles: ``dv += p^T dO``,
    ``dk += ds^T q``.  GQA falls out of the layout: the G query heads of
    kv-head b are rows [b*G, (b+1)*G) of the (B*H, ...) arrays, so one
    leading-axis BlockSpec of size G covers them with no repeat in memory.

Division routing: the ``p = exp(s - m) / l`` renormalization in BOTH
backward kernels runs through :func:`divide_floats_block` with ``l`` as a
``(bq, 1)`` per-row divisor (the rowwise W-word ``DatapathPlan`` path, so
every Table IV variant including posit64 two-word works in the backward
too).  The ``D = rowsum(dO ∘ o)`` correction is computed from the saved
``o`` — whose ``acc / l`` division already ran on the in-kernel SRT
datapath in the forward — with one O(B*H*Sq*hd) elementwise reduce, no
(Sq, Sk) tensor.

Gradients: :func:`posit_flash_attention_ste` wraps the kernels in a
``custom_vjp`` (straight-through the posit quantization, the same STE
convention as the rest of the numerics layer).  ``bwd_impl`` selects the
backward: ``"fused"`` (default) runs the recompute kernels above;
``"reference"`` differentiates a plain float attention reference that
materializes the score tensor — kept for A/B validation only.  Fused vs
reference gradients agree to ~5e-3 abs (posit16; the backward's per-tile p
quantization is ~2^-10 relative) and ~1e-5 abs (posit32/posit64) on the
test sweeps in ``tests/test_flash_attn_kernel.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.posit import PositFormat
from .ops import _on_tpu, _round_up
from .posit_div import (DEFAULT_KERNEL_VARIANT, FLASH_VMEM_LIMIT_BYTES,
                        divide_floats_block)

_NEG_INF = -1e30  # matches the jnp flash path's mask fill
_RES_LANES = 128  # lane width of the row-residual (m, l) kernel outputs


def _minpos_eps(fmt: PositFormat) -> float:
    """Format-aware normalizer epsilon: the format's minpos, clamped to the
    f32 normal range.

    A fully-masked query row accumulates ``l == 0``; dividing by a guaranteed
    -nonzero posit (any float >= minpos quantizes to at least minpos) keeps
    the row at ``0 / eps = 0`` instead of ``0 / 0 -> NaR``.  Tying the value
    to the FORMAT's minpos (2^-max_scale) rather than an arbitrary constant
    keeps it meaningful across posit8..posit64 and documents the invariant.
    """
    return float(2.0 ** -min(fmt.max_scale, 126))


def kv_tile_bounds(kv_start, kv_len, q_first, q_last, *, bk: int,
                   sk_valid: int, causal: bool, window: int, xp=jnp):
    """The KV tiles ``[lo, hi)`` of width ``bk`` that can hold a key some
    query of a q tile attends, for query positions ``q_first..q_last``.

    Every key of a tile outside the range is masked for every query row:
    it lies before ``kv_start``, at or past ``min(kv_len, sk_valid)``,
    after ``q_last`` (``causal``), or at or before ``q_first - window``
    (sliding ``window``).  Skipping such a tile changes nothing in the
    forward kernel's ``(m, l, acc)`` recurrence: its scores are all
    ``_NEG_INF``, so ``m`` stays, ``corr = exp(0) = 1`` and ``p = 0``
    leave ``l`` and ``acc`` as they were.  ``hi <= lo`` means no tile.

    Pure integer arithmetic on nonnegative operands, so it runs on the
    kernel's SMEM scalars (``xp=jnp``) and on host arrays (``xp=np``)
    alike; :func:`decode_rows_scanned` is the host side."""
    lo = xp.maximum(kv_start, 0) // bk
    if window:
        lo = xp.maximum(lo, xp.maximum(q_first - window + 1, 0) // bk)
    end = xp.minimum(kv_len, sk_valid)
    if causal:
        end = xp.minimum(end, q_last + 1)
    hi = (xp.maximum(end, 0) + bk - 1) // bk
    return lo, hi


def _flash_kernel(*refs,
                  fmt: PositFormat, variant: str, causal: bool, window: int,
                  q_offset: int, scale: float, bq: int, bk: int,
                  sk_valid: int, save_res: bool, pages: int = 0,
                  n_heads: int = 0, kv_heads: int = 0, group: int = 1,
                  num_blocks: int = 0, bt_cols: int = 0,
                  has_seg: bool = False):
    if pages:
        # paged mode: k/v refs are the WHOLE block pools in kernel layout
        # (num_blocks * KV, block_size, hdp) plus the flattened (B *
        # bt_cols,) block tables in SMEM; each kv tile is gathered as
        # ``pages`` pool pages
        q_ref, k_ref, v_ref, bt_ref, ks_ref, kl_ref, qp_ref, *out_refs = refs
    else:
        q_ref, k_ref, v_ref, ks_ref, kl_ref, qp_ref, *out_refs = refs
    if has_seg:
        # packed prefill: per-position segment ids ride as the last two
        # inputs (lane-broadcast q rows, sublane-broadcast kv row)
        sq_ref, skv_ref, out_refs = out_refs[0], out_refs[1], out_refs[2:]
    # program_id is read at kernel-body level: the interpreter substitutes
    # it there, and inside the fori_loop jaxpr it would not lower
    bh = pl.program_id(0)
    seq = bh // n_heads                             # batch row of this head
    q = q_ref[0]                                    # (bq, hdp) f32
    kv_start = ks_ref[seq]                          # SMEM scalars (B,) int32
    kv_len = jnp.minimum(kl_ref[seq], sk_valid)     # per-sequence valid rows
    q_first = qp_ref[seq] + q_offset + pl.program_id(1) * bq
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    seg_q = sq_ref[0][:, :1] if has_seg else None   # (bq, 1) int32
    lo, hi = kv_tile_bounds(kv_start, kv_len, q_first, q_first + bq - 1,
                            bk=bk, sk_valid=sk_valid, causal=causal,
                            window=window)

    m0 = jnp.full((bq, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((bq, 1), dtype=jnp.float32)
    a0 = jnp.zeros(q.shape, dtype=jnp.float32)
    kvh = (bh % n_heads) // group if pages else 0

    def kv_step(j, carry):
        m, l, acc = carry
        if pages:
            # Gather this tile's kv rows page by page: logical kv tile j
            # covers table columns [j*pages, (j+1)*pages); each column's
            # block id selects a pool page for this sequence's kv head.
            # Because block_size divides bk and the virtual Sk equals the
            # dense max_seq, the assembled (bk, hdp) tile carries the SAME
            # values in the SAME lane order as the dense-layout load — the
            # (m, l, acc) recurrence below is bit-identical to dense.
            pk, pv = [], []
            for t in range(pages):
                col = jnp.minimum(j * pages + t, bt_cols - 1)
                bid = bt_ref[seq * bt_cols + col]
                row = jnp.clip(bid, 0, num_blocks - 1) * kv_heads + kvh
                pk.append(k_ref[row])
                pv.append(v_ref[row])
            kj = jnp.concatenate(pk, axis=0) if pages > 1 else pk[0]
            vj = jnp.concatenate(pv, axis=0) if pages > 1 else pv[0]
        else:
            kj = k_ref[0, pl.ds(j * bk, bk), :]     # (bk, hdp)
            vj = v_ref[0, pl.ds(j * bk, bk), :]
        # zero the value rows outside [kv_start, kv_len): their p is 0,
        # and 0 * NaN would poison acc.  Finite rows gave exact zeros
        # already, so this changes no result.
        row = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        vj = jnp.where((row < kv_len) & (row >= kv_start), vj, 0.0)
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = (k_pos < kv_len) & (k_pos >= kv_start)
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
        if has_seg:
            # block-diagonal packed-prefill mask: a query may only attend
            # keys of its own segment (pads carry id -1 in both arrays)
            skv_j = skv_ref[0, :1, pl.ds(j * bk, bk)]   # (1, bk) int32
            mask &= seg_q == skv_j
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # only the tiles that can hold an unmasked key: a skipped tile is an
    # exact no-op of the recurrence (see kv_tile_bounds)
    m, l, acc = jax.lax.fori_loop(lo, hi, kv_step, (m0, l0, a0))

    # Final normalizer through the SRT datapath: l is a (bq, 1) per-row
    # divisor, quantized and decoded once per query row (rowwise division).
    # Fully-masked rows have l == 0 and acc == 0: substitute the format's
    # minpos so they normalize to 0 instead of 0/0 -> NaR.
    l_safe = jnp.where(l > 0, l, _minpos_eps(fmt))
    out_refs[0][0] = divide_floats_block(fmt, acc, l_safe, variant)
    if save_res:
        # Row residuals for the recompute backward, broadcast across the
        # lane axis (TPU-tileable): lse = m + log(l) in factored (m, l)
        # form, so the backward can re-run exp(s - m) / l as a posit
        # rowwise division instead of a float exp(s - lse).
        out_refs[1][0] = jnp.broadcast_to(m, (bq, _RES_LANES))
        out_refs[2][0] = jnp.broadcast_to(l, (bq, _RES_LANES))


def _tile_params(Sq, Sk, hd, block_q, block_k):
    """Static tile geometry shared by the forward and backward kernels."""
    bq = min(block_q, _round_up(Sq, 8))
    bk = min(block_k, _round_up(Sk, 8))
    return bq, bk, _round_up(Sq, bq), _round_up(Sk, bk), _round_up(hd, 128)


def _to_kernel_layout(x, Sp, hdp):
    """Transpose/pad one (B, S, nh, hd) tensor into the (B*nh, Sp, hdp)
    kernel layout."""
    B, S, nh, hd = x.shape
    xf = jnp.transpose(x.astype(jnp.float32), (0, 2, 1, 3)).reshape(
        B * nh, S, hd)
    return jnp.pad(xf, ((0, 0), (0, Sp - S), (0, hdp - hd)))


def _pool_kernel_layout(p, hdp):
    """Transpose/pad a (num_blocks, block_size, KV, hd) block pool into the
    (num_blocks * KV, block_size, hdp) kernel layout: block ``b``'s page
    for kv head ``h`` is leading row ``b * KV + h``."""
    NB, bs, KV, hd = p.shape
    pf = jnp.transpose(p.astype(jnp.float32), (0, 2, 1, 3)).reshape(
        NB * KV, bs, hd)
    return jnp.pad(pf, ((0, 0), (0, 0), (0, hdp - hd)))


def _flash_call(fmt, q, k, v, causal, window, q_offset, scale, variant,
                interpret, block_q, block_k, vmem_limit_bytes, save_res,
                kv_start, kv_len=None, q_pos=None, block_tables=None,
                seg_q=None, seg_kv=None):
    if interpret is None:
        interpret = not _on_tpu()
    B, Sq, H, hd = q.shape
    paged = block_tables is not None
    if paged:
        # k/v are global block pools (num_blocks, block_size, KV, hd);
        # the virtual kv length is the table width times the block size,
        # which the engine keeps equal to the dense max_seq — so the tile
        # geometry (bq, bk) below matches the dense layout exactly and
        # the kv scan accumulates bit-identically.
        NB, bsz, KV, _ = k.shape
        Sk = block_tables.shape[1] * bsz
    else:
        _, Sk, KV, _ = k.shape
    assert k.shape == v.shape and H % KV == 0, (q.shape, k.shape)
    G = H // KV
    if scale <= 0.0:
        scale = 1.0 / math.sqrt(hd)

    bq, bk, Sqp, Skp, hdp = _tile_params(Sq, Sk, hd, block_q, block_k)
    qf = _to_kernel_layout(q, Sqp, hdp)
    paged_kw = {}
    if paged:
        assert not save_res, "paged attention is forward/decode-only"
        assert bk % bsz == 0, (
            f"block_size {bsz} must divide the kv tile {bk} "
            "(power of two <= 128)")
        kf = _pool_kernel_layout(k, hdp)
        vf = _pool_kernel_layout(v, hdp)
        # the kernel holds both whole pools in VMEM, double-buffered; a
        # pool past the budget is refused here rather than by Mosaic
        pool_vmem = 4 * kf.size * kf.dtype.itemsize
        if not interpret and pool_vmem > vmem_limit_bytes:
            raise ValueError(
                f"paged fused attention keeps the whole KV block pools in "
                f"VMEM: {pool_vmem} bytes for {NB} blocks of {bsz} rows x "
                f"{KV} kv heads, over the {vmem_limit_bytes}-byte budget; "
                "serve this size with the dense KV layout or "
                "attn_backend='xla'")
        btf = block_tables.astype(jnp.int32).reshape(-1)
        paged_kw = dict(pages=bk // bsz, kv_heads=KV, group=G,
                        num_blocks=NB, bt_cols=block_tables.shape[1])
    else:
        kf = _to_kernel_layout(k, Skp, hdp)
        vf = _to_kernel_layout(v, Skp, hdp)

    def _per_seq(vec, default):
        """(B,) per-sequence int32 scalars; the kernel reads them from SMEM."""
        if vec is None:
            return jnp.full((B,), default, jnp.int32)
        return vec.astype(jnp.int32).reshape(B)

    ksf = _per_seq(kv_start, 0)
    klf = _per_seq(kv_len, Sk)
    qpf = _per_seq(q_pos, 0)

    has_seg = seg_q is not None
    seg_inputs, seg_specs = (), []
    if has_seg:
        # Per-position segment ids for packed prefill.  Laid out tileable:
        # q segments lane-broadcast to (B, Sqp, _RES_LANES) and read back
        # as a (bq, 1) column; kv segments sublane-broadcast to
        # (B, 8, Skp) so each kv tile slices a (1, bk) row.  Layout pad
        # positions get id -1 (they are already masked by kv_len/causal).
        assert seg_kv is not None and seg_q.shape == (B, Sq), seg_q.shape
        sqp = jnp.pad(seg_q.astype(jnp.int32), ((0, 0), (0, Sqp - Sq)),
                      constant_values=-1)
        skp = jnp.pad(seg_kv.astype(jnp.int32), ((0, 0), (0, Skp - Sk)),
                      constant_values=-1)
        seg_inputs = (
            jnp.broadcast_to(sqp[:, :, None], (B, Sqp, _RES_LANES)),
            jnp.broadcast_to(skp[:, None, :], (B, 8, Skp)),
        )
        seg_specs = [
            pl.BlockSpec((1, bq, _RES_LANES), lambda b, i: (b // H, i, 0)),
            pl.BlockSpec((1, 8, Skp), lambda b, i: (b // H, 0, 0)),
        ]

    kernel = functools.partial(
        _flash_kernel, fmt=fmt, variant=variant, causal=causal,
        window=window, q_offset=int(q_offset), scale=scale, bq=bq, bk=bk,
        sk_valid=Sk, save_res=save_res, has_seg=has_seg, n_heads=H,
        **paged_kw)
    out_shape = [jax.ShapeDtypeStruct((B * H, Sqp, hdp), jnp.float32)]
    out_specs = [pl.BlockSpec((1, bq, hdp), lambda b, i: (b, i, 0))]
    if save_res:
        out_shape += 2 * [jax.ShapeDtypeStruct((B * H, Sqp, _RES_LANES),
                                               jnp.float32)]
        out_specs += 2 * [pl.BlockSpec((1, bq, _RES_LANES),
                                       lambda b, i: (b, i, 0))]
    # per-sequence scalars and block tables: whole arrays in SMEM
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if paged:
        # the pools ride along whole (constant index map) — pages are
        # gathered in-kernel from the per-sequence block-table row
        kv_specs = [pl.BlockSpec(kf.shape, lambda b, i: (0, 0, 0))] * 2
        inputs = (qf, kf, vf, btf, ksf, klf, qpf) + seg_inputs
        extra = [smem]
    else:
        kv_specs = 2 * [pl.BlockSpec(
            (1, Skp, hdp), lambda b, i: (b // H * KV + (b % H) // G, 0, 0))]
        inputs = (qf, kf, vf, ksf, klf, qpf) + seg_inputs
        extra = []
    outs = pl.pallas_call(
        kernel,
        name="posit_flash_attention",
        out_shape=out_shape,
        grid=(B * H, Sqp // bq),
        in_specs=[pl.BlockSpec((1, bq, hdp), lambda b, i: (b, i, 0))]
        + kv_specs + extra + 3 * [smem] + seg_specs,
        out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(*inputs)

    out = outs[0][:, :Sq, :hd].reshape(B, H, Sq, hd)
    out = jnp.transpose(out, (0, 2, 1, 3))
    if not save_res:
        return out
    # (B*H, Sqp) row residuals, kept PADDED so the backward kernels can
    # consume them with the same (block_q-derived) tiling.
    return out, outs[1][:, :, 0], outs[2][:, :, 0]


@functools.partial(jax.jit,
                   static_argnums=(0,) + tuple(range(4, 13)))
def posit_flash_attention(
    fmt: PositFormat,
    q,
    k,
    v,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float = 0.0,
    variant: str = DEFAULT_KERNEL_VARIANT,
    interpret: bool = None,
    block_q: int = 128,
    block_k: int = 128,
    vmem_limit_bytes: int = FLASH_VMEM_LIMIT_BYTES,
    kv_start=None,
    kv_len=None,
    q_pos=None,
    block_tables=None,
    seg_q=None,
    seg_kv=None,
):
    """Flash attention with the posit SRT normalizer, one kernel launch.

    ``q``: (B, Sq, H, hd); ``k``/``v``: (B, Sk, KV, hd) with H % KV == 0
    (GQA via the index map — no repeated KV in memory).  All compute f32.
    ``scale`` <= 0 means the default 1/sqrt(hd); ``interpret=None``
    auto-selects (interpret off TPU, compiled on TPU) like the other
    kernel wrappers.

    ``kv_start``/``kv_len``/``q_pos`` are optional (B,) int32 per-sequence
    arrays for slot-based serving: key positions outside
    ``[kv_start[b], kv_len[b])`` are masked, and ``q_pos[b]`` offsets the
    sequence's query positions in the causal/window masks (on top of the
    static ``q_offset``).  The serving engine's per-slot decode passes
    ``q_pos = pos`` and ``kv_len = pos + 1`` so every slot attends exactly
    its own written cache rows at its own offset, in one compiled kernel.
    Tiles that lie wholly outside ``[kv_start, kv_len)`` (or wholly past
    the causal / window reach of a q tile) are not scanned at all: every
    key in them is masked, which makes them exact no-ops of the online
    softmax, so the result is bit-identical to scanning them.  Value rows
    outside the range are zeroed in the tiles that are scanned, so
    non-finite values there cannot leak into the output.

    ``block_tables`` switches the kv side to the PAGED layout: ``k``/``v``
    become global block pools ``(num_blocks, block_size, KV, hd)`` and
    ``block_tables`` is a per-sequence ``(B, max_blocks)`` int32 table
    mapping logical kv row ``r`` of sequence ``b`` to pool row
    ``(block_tables[b, r // block_size], r % block_size)``.  Paging is an
    index-map change, not a new kernel family: the kv scan gathers
    ``bk / block_size`` pages per tile inside the same (m, l, acc)
    recurrence, and with ``max_blocks * block_size`` equal to the dense
    path's Sk the tile geometry — hence every accumulation — is
    bit-identical to the dense layout.  Forward/decode only (no saved
    residuals); block_size must be a power of two that divides the kv
    tile (<= ``block_k``).

    ``seg_q``/``seg_kv`` are optional PER-POSITION ``(B, Sq)``/``(B, Sk)``
    int32 segment-id arrays for packed multi-prompt prefill: when given,
    the score mask additionally requires ``seg_q[b, i] == seg_kv[b, j]``,
    making causal attention over a concatenation of prompts
    block-diagonal.  Pad positions carry id -1 in both arrays.  Masked
    entries contribute exact zeros to the (m, l, acc) recurrence, so each
    segment's rows are bit-identical to running that prompt alone with
    the same tile geometry.
    """
    return _flash_call(fmt, q, k, v, causal, window, q_offset, scale,
                       variant, interpret, block_q, block_k,
                       vmem_limit_bytes, False, kv_start, kv_len, q_pos,
                       block_tables, seg_q, seg_kv)


def decode_rows_scanned(pos, start, max_seq: int, block_k: int = 128):
    """Per slot, the KV rows in the tiles one decode launch scans, on the
    host: the kernel's own :func:`kv_tile_bounds` for the serving decode
    call (one query at ``q_pos = pos``, ``kv_len = pos + 1``, ``kv_start =
    start``, causal, no window, ``max_seq`` keys in tiles of ``block_k``),
    whole tiles counted."""
    pos = np.asarray(pos, np.int64)
    bq, bk = _tile_params(1, max_seq, 1, 128, block_k)[:2]
    lo, hi = kv_tile_bounds(np.asarray(start, np.int64), pos + 1, pos,
                            pos + bq - 1, bk=bk, sk_valid=max_seq,
                            causal=True, window=0, xp=np)
    return np.maximum(hi - lo, 0) * bk


@functools.partial(jax.jit,
                   static_argnums=(0,) + tuple(range(4, 13)))
def posit_flash_attention_fwd(
    fmt: PositFormat,
    q,
    k,
    v,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float = 0.0,
    variant: str = DEFAULT_KERNEL_VARIANT,
    interpret: bool = None,
    block_q: int = 128,
    block_k: int = 128,
    vmem_limit_bytes: int = FLASH_VMEM_LIMIT_BYTES,
):
    """Forward pass that also returns the recompute-backward residuals.

    Returns ``(o, m, l)``: the attention output plus the per-row online-
    softmax max and sum in the (B*H, Sq_padded) kernel layout — O(B*H*Sq)
    memory, the factored form of the row logsumexp ``lse = m + log l``.
    """
    return _flash_call(fmt, q, k, v, causal, window, q_offset, scale,
                       variant, interpret, block_q, block_k,
                       vmem_limit_bytes, True, None)


# =====================================================================
# fused recompute backward
# =====================================================================


def _bwd_tile(fmt, variant, q, go, kj, vj, mrow, l_safe, drow, mask, scale):
    """Shared per-tile backward math: returns (p, ds) for one score tile.

    ``p = exp(s - m) / l`` runs through the in-kernel SRT datapath as a
    rowwise posit division (``l`` is a (bq, 1) column); masked entries are
    exact zeros on both sides of the divide (0 / l == 0 in posit).
    """
    s = jax.lax.dot_general(
        q, kj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (bq, bk)
    e = jnp.where(mask, jnp.exp(s - mrow), 0.0)
    p = divide_floats_block(fmt, e, l_safe, variant)
    dp = jax.lax.dot_general(
        go, vj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (bq, bk)
    ds = p * (dp - drow)
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, d_ref,
                         dq_ref, *, fmt: PositFormat, variant: str,
                         causal: bool, window: int, q_offset: int,
                         scale: float, bq: int, bk: int, nk: int,
                         sk_valid: int):
    q = q_ref[0]                                    # (bq, hdp)
    go = g_ref[0]
    mrow = m_ref[0][:, :1]                          # (bq, 1)
    lrow = l_ref[0][:, :1]
    drow = d_ref[0][:, :1]
    l_safe = jnp.where(lrow > 0, lrow, _minpos_eps(fmt))
    iq = pl.program_id(1)
    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, 1), 0)

    def kv_step(j, dq):
        kj = k_ref[0, pl.ds(j * bk, bk), :]
        vj = v_ref[0, pl.ds(j * bk, bk), :]
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = k_pos < sk_valid
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
        _, ds = _bwd_tile(fmt, variant, q, go, kj, vj, mrow, l_safe, drow,
                          mask, scale)
        return dq + jax.lax.dot_general(
            ds, kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk, kv_step, jnp.zeros_like(q))
    dq_ref[0] = dq * scale


def _flash_bwd_dkv_kernel(q_ref, g_ref, m_ref, l_ref, d_ref, k_ref, v_ref,
                          dk_ref, dv_ref, *, fmt: PositFormat, variant: str,
                          causal: bool, window: int, q_offset: int,
                          scale: float, bq: int, bk: int, nq: int, G: int,
                          sk_valid: int):
    kj = k_ref[0]                                   # (bk, hdp)
    vj = v_ref[0]
    jk = pl.program_id(1)
    k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    k_mask = k_pos < sk_valid

    def q_step(t, carry):
        dk, dv = carry
        g, i = t // nq, t % nq
        rows = pl.ds(i * bq, bq)
        q = q_ref[g, rows, :]                       # (bq, hdp)
        go = g_ref[g, rows, :]
        mrow = m_ref[g, rows, :][:, :1]             # (bq, 1)
        lrow = l_ref[g, rows, :][:, :1]
        drow = d_ref[g, rows, :][:, :1]
        l_safe = jnp.where(lrow > 0, lrow, _minpos_eps(fmt))
        q_pos = q_offset + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0)
        mask = k_mask
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
        p, ds = _bwd_tile(fmt, variant, q, go, kj, vj, mrow, l_safe, drow,
                          mask, scale)
        dv_new = dv + jax.lax.dot_general(
            p, go, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # (bk, hdp)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    z = jnp.zeros_like(kj)
    dk, dv = jax.lax.fori_loop(0, G * nq, q_step, (z, z))
    dk_ref[0] = dk * scale
    dv_ref[0] = dv


@functools.partial(jax.jit, static_argnums=(0,) + tuple(range(8, 17)))
def _flash_backward(fmt: PositFormat, q, k, v, o, g, m, l,
                    causal: bool, window: int, q_offset: int, scale: float,
                    variant: str, interpret: bool = None,
                    block_q: int = 128, block_k: int = 128,
                    vmem_limit_bytes: int = FLASH_VMEM_LIMIT_BYTES):
    """Recompute-style fused backward: (dq, dk, dv) from the saved row
    residuals, with no (Sq, Sk) intermediate anywhere.

    ``m``/``l`` are the (B*H, Sq_padded) residuals from
    :func:`posit_flash_attention_fwd` (same ``block_q`` so the padding
    agrees); ``o``/``g`` are the forward output and its cotangent in the
    user (B, Sq, H, hd) layout.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    if scale <= 0.0:
        scale = 1.0 / math.sqrt(hd)

    bq, bk, Sqp, Skp, hdp = _tile_params(Sq, Sk, hd, block_q, block_k)
    qf = _to_kernel_layout(q, Sqp, hdp)
    kf = _to_kernel_layout(k, Skp, hdp)
    vf = _to_kernel_layout(v, Skp, hdp)
    gf = _to_kernel_layout(g, Sqp, hdp)
    nq, nk = Sqp // bq, Skp // bk
    assert m.shape == (B * H, Sqp), (m.shape, (B * H, Sqp))

    # D = rowsum(dO ∘ o): the o here is the posit forward output, whose
    # acc/l division already ran on the in-kernel SRT datapath.  One
    # O(B*H*Sq*hd) reduce — never a score tensor.
    D = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    D = jnp.pad(jnp.transpose(D, (0, 2, 1)).reshape(B * H, Sq),
                ((0, 0), (0, Sqp - Sq)))

    def rows(x):  # (B*H, Sqp) -> lane-broadcast (B*H, Sqp, _RES_LANES)
        return jnp.broadcast_to(x[:, :, None], (B * H, Sqp, _RES_LANES))

    mb, lb, Db = rows(m), rows(l), rows(D)
    params = pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)
    kv_map = lambda b, i: (b // H * KV + (b % H) // G, 0, 0)  # noqa: E731
    row_spec = pl.BlockSpec((1, bq, _RES_LANES), lambda b, i: (b, i, 0))

    dqf = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, fmt=fmt, variant=variant, causal=causal,
            window=window, q_offset=q_offset, scale=scale, bq=bq, bk=bk,
            nk=nk, sk_valid=Sk),
        name="posit_flash_attention_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, hdp), jnp.float32),
        grid=(B * H, nq),
        in_specs=[
            pl.BlockSpec((1, bq, hdp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Skp, hdp), kv_map),
            pl.BlockSpec((1, Skp, hdp), kv_map),
            pl.BlockSpec((1, bq, hdp), lambda b, i: (b, i, 0)),
            row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, hdp), lambda b, i: (b, i, 0)),
        compiler_params=params,
        interpret=interpret,
    )(qf, kf, vf, gf, mb, lb, Db)

    # The G query heads of kv-head b are rows [b*G, (b+1)*G) of the
    # (B*H, ...) arrays (h = kv*G + g), so a leading-axis block of size G
    # at block index b covers exactly them.
    g_spec = pl.BlockSpec((G, Sqp, hdp), lambda b, j: (b, 0, 0))
    g_rows = pl.BlockSpec((G, Sqp, _RES_LANES), lambda b, j: (b, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, hdp), lambda b, j: (b, j, 0))
    dkf, dvf = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, fmt=fmt, variant=variant, causal=causal,
            window=window, q_offset=q_offset, scale=scale, bq=bq, bk=bk,
            nq=nq, G=G, sk_valid=Sk),
        name="posit_flash_attention_bwd_dkv",
        out_shape=2 * [jax.ShapeDtypeStruct((B * KV, Skp, hdp),
                                            jnp.float32)],
        grid=(B * KV, nk),
        in_specs=[g_spec, g_spec, g_rows, g_rows, g_rows, kv_spec, kv_spec],
        out_specs=[kv_spec, kv_spec],
        compiler_params=params,
        interpret=interpret,
    )(qf, gf, mb, lb, Db, kf, vf)

    def to_user(x, S, nh):
        x = x[:, :S, :hd].reshape(B, nh, S, hd)
        return jnp.transpose(x, (0, 2, 1, 3))

    return (to_user(dqf, Sq, H).astype(q.dtype),
            to_user(dkf, Sk, KV).astype(k.dtype),
            to_user(dvf, Sk, KV).astype(v.dtype))


# =====================================================================
# differentiable wrapper (STE custom_vjp)
# =====================================================================


def _attention_reference(q, k, v, causal, window, q_offset, scale):
    """Differentiable float attention (plain softmax/divide) for the A/B
    reference backward; numerics mirror the jnp flash path with exact
    division.  Materializes the (Sq, Sk) score tensor — validation only."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qg = q.astype(jnp.float32).reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def _flash_ste(bwd_impl: str, fmt_n: int, variant: str, causal: bool,
               window: int, q_offset: int, scale: float, q, k, v):
    return posit_flash_attention(
        PositFormat(fmt_n), q, k, v, causal, window, q_offset, scale,
        variant)


def _flash_ste_fwd(bwd_impl, fmt_n, variant, causal, window, q_offset,
                   scale, q, k, v):
    if bwd_impl == "reference":
        out = posit_flash_attention(
            PositFormat(fmt_n), q, k, v, causal, window, q_offset, scale,
            variant)
        return out, (q, k, v, None, None, None)
    out, m, l = posit_flash_attention_fwd(
        PositFormat(fmt_n), q, k, v, causal, window, q_offset, scale,
        variant)
    return out, (q, k, v, out, m, l)


def _flash_ste_bwd(bwd_impl, fmt_n, variant, causal, window, q_offset,
                   scale, res, g):
    q, k, v, o, m, l = res
    if scale <= 0.0:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if bwd_impl == "reference":
        _, vjp = jax.vjp(
            lambda q, k, v: _attention_reference(q, k, v, causal, window,
                                                 q_offset, scale), q, k, v)
        return vjp(g.astype(jnp.float32))
    return _flash_backward(PositFormat(fmt_n), q, k, v, o, g, m, l,
                           causal, window, q_offset, scale, variant)


_flash_ste.defvjp(_flash_ste_fwd, _flash_ste_bwd)


def posit_flash_attention_ste(fmt_n: int, variant: str, causal: bool,
                              window: int, q_offset: int, scale: float,
                              q, k, v, bwd_impl: str = "fused"):
    """Differentiable wrapper: fused posit kernel forward, recompute fused
    backward (``bwd_impl="fused"``, default) or float-reference STE
    backward (``bwd_impl="reference"``, A/B validation only — it
    materializes the (Sq, Sk) score tensor the flash pattern avoids)."""
    assert bwd_impl in ("fused", "reference"), bwd_impl
    return _flash_ste(bwd_impl, fmt_n, variant, causal, window, q_offset,
                      scale, q, k, v)
