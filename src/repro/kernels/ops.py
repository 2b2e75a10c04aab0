"""Public jit'd wrappers around the Pallas posit kernels.

Handles arbitrary input shapes (flatten -> pad to block multiples -> kernel
-> unpad), backend selection (interpret mode on CPU, compiled on TPU), and
exposes the same signatures as the pure-jnp references in :mod:`ref`.

Dispatch rules for the fused division family (what the numerics layer's
``posit_div_values`` / ``posit_softmax`` select, in priority order):

  1. **softmax-fused** (:func:`posit_softmax_fused`) — the whole stable
     softmax (row max, exp, row sum, SRT divide) when the caller IS a
     softmax over one axis.  One launch, reductions never leave VMEM.
  2. **rowwise** (:func:`posit_div_fused_rowwise`) — ``a / b`` where ``b``
     broadcasts against ``a`` with a size-1 (or missing) last axis and ``a``
     has a real last axis: softmax/router denominators, RMSNorm
     reciprocals, the flash-attention ``o / l`` normalizer.  The divisor is
     carried as a ``(rows, 1)`` column; its quantize/decode/selection-index
     work runs once per row and no broadcast denominator touches HBM.
  3. **elementwise** (:func:`posit_div_fused`) — same-shape operands; both
     are tiled at full width (PR 1's kernel).

All three are bit-identical to the chained
``posit_quantize -> posit_div -> posit_dequantize`` path (and therefore to
the BitVec ``emulate`` backend) for every (format, variant) with a datapath
plan (:func:`repro.kernels.posit_div.kernel_datapath_plan`): all Table IV
rows — ``nrd``, ``srt_r2``, the carry-save/OTF ladder, ``srt_r4_scaled`` —
on a 1- or 2-word residual frame.  Posit64 runs the two-word plan through
the float-level entry points (its 60-bit significand spans two words);
``srt_r4_scaled`` is planless only above n = 62, where its 3 extra
operand-scaling fraction bits overflow the two-word frame.  Unsupported
combinations raise with the reason derived from the plan
(:func:`repro.kernels.posit_div.kernel_plan_error`), so the messages stay
truthful as the plan table evolves.

The pattern-level :func:`posit_div` is the one n <= 32 API (wide patterns do
not fit a uint32 word); the float-in/float-out fused entry points accept
every planned format including posit64.

The softmax kernel's f32 row SUM runs in FIXED left-to-right order
(:func:`repro.core.quire.fixed_order_lanesum`, the unrolled kernel form of
the emulate path's :func:`repro.core.quire.fixed_order_rowsum`):
appended pad zeros are additive identities at every partial sum, so the
padded in-kernel reduction is bit-identical to the unpadded emulate one —
for every format including posit64, which keeps all f32 mantissa bits and
used to disagree by 1 ulp when the two sums were free-order ``jnp.sum``.

Padding convention: dividend lanes pad with 0, **divisor lanes pad with 1**
(float 1.0, posit pattern ``0b01…0``), so padding computes ``0 / 1 = 0``
instead of ``0 / 0 -> NaR/NaN`` and the fused paths stay clean under
``jax.debug_nans``.
"""

from __future__ import annotations


import jax.numpy as jnp
import numpy as np

from repro.core.posit import PositFormat
from . import posit_div as _div
from . import posit_cast as _cast
from . import posit_fused_div as _fused

DEFAULT_DIV_VARIANT = _div.DEFAULT_KERNEL_VARIANT
FUSED_DIV_VARIANTS = _div.KERNEL_VARIANTS

_DEFAULT_BLOCK = (64, 256)
_ROW_BLOCK = 64    # preferred row tile for the rowwise/softmax kernels
# Largest tile (elements) a divider kernel body takes: the datapath keeps
# ~100 block-sized int32 temporaries live, and at 64 x 256 that fits the
# VMEM budget (posit_div.VMEM_LIMIT_BYTES).  Wide rows get fewer rows.
_TILE_ELEMS = 64 * 256
_LANE = 128        # TPU lane width: last-dim padding multiple


def fused_variant_supported(fmt: PositFormat, variant: str) -> bool:
    """Does (fmt, variant) have a single-kernel fused datapath plan?"""
    return _div.kernel_variant_supported(fmt, variant)


def _check_fused(fmt: PositFormat, variant: str) -> None:
    """Raise with the plan-derived reason when no fused datapath exists."""
    err = _div.kernel_plan_error(fmt, variant)
    if err is not None:
        raise ValueError(f"no fused datapath: {err}")


def _on_tpu() -> bool:
    return _div.on_tpu()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_2d(x, block, pad_value=0):
    """Flatten to (rows, bn) padded to block multiples; return unpad info.

    ``pad_value`` fills the padding lanes — divisor arrays pass 1 (float
    1.0 or the posit +1 bit pattern) so padding divides ``0 / 1`` instead
    of ``0 / 0 -> NaR``.
    """
    bm, bn = block
    flat = x.reshape(-1)
    total = flat.shape[0]
    cols = bn
    rows = -(-total // cols)
    rows_pad = -(-rows // bm) * bm
    pad = rows_pad * cols - total
    flat = jnp.pad(flat, (0, pad), constant_values=pad_value)
    return flat.reshape(rows_pad, cols), total


def _row_block(R: int, width: int) -> int:
    """Row-tile height: sublane-aligned, capped by the (padded) row count
    and by the tile budget at this tile width."""
    fit = max(8, _TILE_ELEMS // width // 8 * 8)
    return min(_ROW_BLOCK, _round_up(R, 8), fit)


def _row_tile(a2, b2):
    """Pad (R, C) dividend + (R, 1) divisor to row/lane multiples.

    Dividend pads with 0, divisor rows pad with 1 -> padding lanes compute
    0/1 = 0 (no NaR/NaN under jax.debug_nans).  Returns padded arrays, the
    block shape, and the original (R, C).
    """
    R, C = a2.shape
    Cp = _round_up(C, _LANE)
    bn = max(b for b in (512, 256, _LANE) if Cp % b == 0)
    bm = _row_block(R, bn)
    Rp = _round_up(R, bm)
    a2 = jnp.pad(a2, ((0, Rp - R), (0, Cp - C)))
    b2 = jnp.pad(b2, ((0, Rp - R), (0, 0)), constant_values=1.0)
    return a2, b2, (bm, bn), (R, C)


def rowwise_applicable(a_shape, b_shape) -> bool:
    """Is ``a / b`` a row-broadcast division the rowwise kernel can take?

    True when ``b`` broadcasts into ``a`` with a size-1 (or absent) last
    axis while ``a``'s last axis is real — i.e. one divisor per row and no
    materialized broadcast needed.
    """
    a_shape, b_shape = tuple(a_shape), tuple(b_shape)
    if len(a_shape) == 0 or a_shape[-1] <= 1:
        return False
    if len(b_shape) > len(a_shape):
        return False
    if b_shape and b_shape[-1] != 1:
        return False
    try:
        out = np.broadcast_shapes(a_shape, b_shape)
    except ValueError:
        return False
    return out == a_shape


def posit_div(fmt: PositFormat, px, pd, block=_DEFAULT_BLOCK, interpret=None,
              variant: str = DEFAULT_DIV_VARIANT):
    """Elementwise posit division on bit-pattern arrays (n <= 32, any shape)."""
    if fmt.n > 32:
        raise ValueError(
            f"posit_div takes uint32 bit patterns, which cannot hold {fmt}; "
            "wide formats divide through the float-level fused entry points "
            "(posit_div_fused / posit_div_fused_rowwise / posit_softmax_fused)")
    _check_fused(fmt, variant)
    if interpret is None:
        interpret = not _on_tpu()
    shape = px.shape
    x2, total = _tile_2d(px.astype(jnp.uint32), block)
    # divisor padding = posit +1 pattern: padding lanes divide 0/1 = 0.
    one = 1 << (fmt.n - 2)
    d2, _ = _tile_2d(pd.astype(jnp.uint32), block, pad_value=one)
    out = _div.posit_div_pallas(fmt, x2, d2, block, interpret, variant=variant)
    return out.reshape(-1)[:total].reshape(shape)


def posit_div_fused(fmt: PositFormat, a, b, block=_DEFAULT_BLOCK,
                    interpret=None, variant: str = DEFAULT_DIV_VARIANT):
    """Fused quantize -> divide -> dequantize: float32 in, float32 out.

    One kernel launch; bit-identical to
    ``posit_dequantize(posit_div(posit_quantize(a), posit_quantize(b)))``.
    """
    _check_fused(fmt, variant)
    if interpret is None:
        interpret = not _on_tpu()
    shape = a.shape
    a2, total = _tile_2d(a.astype(jnp.float32), block)
    # divisor padding = 1.0: padding lanes divide 0/1 = 0, not 0/0 -> NaR.
    b2, _ = _tile_2d(b.astype(jnp.float32), block, pad_value=1.0)
    out = _fused.posit_fused_div_pallas(fmt, a2, b2, block, interpret,
                                        variant=variant)
    return out.reshape(-1)[:total].reshape(shape)


def posit_div_fused_rowwise(fmt: PositFormat, a, b, interpret=None,
                            variant: str = DEFAULT_DIV_VARIANT):
    """Row-broadcast fused division: ``a[..., C] / b[..., 1]`` in one launch.

    ``b`` may be any shape that broadcasts against ``a`` with a size-1 (or
    missing) last axis (see :func:`rowwise_applicable`).  The divisor is
    expanded only across its *leading* axes to ``a.shape[:-1] + (1,)`` — an
    O(rows) array — and rides into the kernel as a per-row column, so the
    O(rows * C) broadcast of the chained path never materializes.
    Bit-identical to ``posit_div_fused(a, broadcast(b))``.
    """
    _check_fused(fmt, variant)
    if not rowwise_applicable(a.shape, jnp.shape(b)):
        raise ValueError(
            f"rowwise division needs a per-row divisor; got a.shape="
            f"{a.shape}, b.shape={jnp.shape(b)}")
    if interpret is None:
        interpret = not _on_tpu()
    shape = a.shape
    C = shape[-1]
    a2 = a.astype(jnp.float32).reshape(-1, C)
    bcol = jnp.broadcast_to(jnp.asarray(b, jnp.float32),
                            shape[:-1] + (1,)).reshape(-1, 1)
    a2, b2, block, (R, _) = _row_tile(a2, bcol)
    out = _fused.posit_fused_div_rowwise_pallas(
        fmt, a2, b2, block, interpret, variant=variant)
    return out[:R, :C].reshape(shape)


def posit_softmax_fused(fmt: PositFormat, x, interpret=None,
                        variant: str = DEFAULT_DIV_VARIANT):
    """Single-launch posit softmax over the LAST axis of ``x``.

    Row max, ``exp``, row sum and the SRT divide all happen inside one
    ``pallas_call`` on row-aligned tiles; bit-identical to
    ``posit_div_fused(exp(x - max), sum(exp(x - max)))`` and hence to the
    chained emulate path.
    """
    _check_fused(fmt, variant)
    if interpret is None:
        interpret = not _on_tpu()
    shape = x.shape
    C = shape[-1]
    x2 = x.astype(jnp.float32).reshape(-1, C)
    R = x2.shape[0]
    Cp = _round_up(C, _LANE)
    bm = _row_block(R, Cp)
    Rp = _round_up(R, bm)
    x2 = jnp.pad(x2, ((0, Rp - R), (0, Cp - C)))
    out = _fused.posit_softmax_fused_pallas(fmt, x2, C, bm,
                                            interpret, variant=variant)
    return out[:R, :C].reshape(shape)


def posit_quantize(fmt: PositFormat, x, block=_DEFAULT_BLOCK, interpret=None):
    """float32 -> posit bit patterns (any shape)."""
    if interpret is None:
        interpret = not _on_tpu()
    shape = x.shape
    x2, total = _tile_2d(x.astype(jnp.float32), block)
    out = _cast.posit_quantize_pallas(fmt, x2, block, interpret)
    return out.reshape(-1)[:total].reshape(shape)


def posit_dequantize(fmt: PositFormat, p, block=_DEFAULT_BLOCK, interpret=None):
    """posit bit patterns -> float32 (any shape)."""
    if interpret is None:
        interpret = not _on_tpu()
    shape = p.shape
    p2, total = _tile_2d(p.astype(jnp.uint32), block)
    out = _cast.posit_dequantize_pallas(fmt, p2, block, interpret)
    return out.reshape(-1)[:total].reshape(shape)
