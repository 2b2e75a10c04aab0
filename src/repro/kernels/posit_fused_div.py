"""Pallas TPU kernels: fused float32 -> posit -> SRT divide -> float32.

The numerics layer's hot path (`posit_div_values` behind softmax / RMSNorm /
MoE-router normalization) is a chain of three elementwise kernels:

    posit_quantize(a), posit_quantize(b)  ->  posit_div_pallas  ->
    posit_dequantize

which launches 4 kernels and round-trips two bit-pattern arrays through HBM
between every stage.  This module fuses the whole chain into ONE
``pallas_call``: quantization (RNE float->posit), the folded-first-iteration
W-word SRT recurrence, and dequantization all happen in-register on each
VMEM block — no intermediate posit arrays ever materialize.

Three kernels, by broadcast structure of the division:

  * :func:`posit_fused_div_pallas`          — elementwise ``a / b``, both
    operands full ``(rows, cols)`` arrays.  PR 1's kernel.
  * :func:`posit_fused_div_rowwise_pallas`  — ``(rows, cols) / (rows, 1)``.
    The per-row divisor rides in as a ``(bm, 1)`` block; its quantization,
    decode, ``didx`` selection index, and operand-scaling terms are computed
    once per ROW instead of once per element, and the broadcast never
    materializes in HBM.  This is the shape of every model-level use
    (softmax denominator, RMSNorm reciprocal, router normalizer,
    flash-attention ``o / l``).
  * :func:`posit_softmax_fused_pallas`      — the whole numerically-stable
    softmax (row max, ``exp``, row sum, SRT divide) over row-aligned tiles
    in a single launch.  The tile holds complete rows, so the reductions
    stay in VMEM and the only HBM traffic is the input and output.

Every kernel body composes :func:`repro.kernels.posit_div.divide_floats_block`,
which lowers through the (fmt, variant) datapath plan: the uint32 pattern
datapath for n <= 32 and the two-word significand/residual datapath above it
(posit64).  Bit-exactness: the float path literally runs the same
quantize / recurrence / encode primitives the chained and emulate paths run
(broadcasting is exact: all datapath ops are elementwise), so outputs are
bit-identical by construction — verified by ``tests/test_fused_div.py`` /
``tests/test_rowwise_div.py`` / ``tests/test_multiword_div.py`` against the
chained and BitVec-emulate paths for every planned variant.  Mirrors how
FPPU (arXiv:2308.03425) / PVU (arXiv:2503.01313) integrate posit division as
one pipelined unit instead of a chain of format conversions.

Variant support is the datapath plan's (:mod:`repro.kernels.posit_div`):
every Table IV row, with ``srt_r4_scaled`` limited to n <= 62 (its 3 extra
operand-scaling fraction bits must fit the two-word residual frame).

``interpret=None`` (the default everywhere) auto-selects: interpret mode off
TPU, compiled on TPU — direct kernel callers get the same backend selection
as the :mod:`repro.kernels.ops` wrappers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.posit import PositFormat
from repro.core.quire import fixed_order_lanesum
from .posit_div import (
    DEFAULT_KERNEL_VARIANT,
    VMEM_LIMIT_BYTES,
    divide_floats_block,
    resolve_interpret,
)

_U32 = jnp.uint32

# Logit sentinel for masked/padded softmax lanes: far below any finite f32
# logit but finite itself, so padded rows never produce Inf/NaN intermediates
# (keeps the kernel clean under jax.debug_nans).
_NEG_HUGE = -3.4e38


def _compiler_params(vmem_limit_bytes: int):
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)


def _fused_kernel(a_ref, b_ref, o_ref, *, fmt: PositFormat, variant: str):
    o_ref[...] = divide_floats_block(fmt, a_ref[...], b_ref[...], variant)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6))
def posit_fused_div_pallas(
    fmt: PositFormat,
    a,
    b,
    block=(64, 256),
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = VMEM_LIMIT_BYTES,
    variant: str = DEFAULT_KERNEL_VARIANT,
):
    """Tiled fused divider over 2D float32 arrays (pre-padded by ops.py)."""
    assert a.ndim == 2 and a.shape == b.shape
    interpret = resolve_interpret(interpret)
    bm, bn = block
    m, n = a.shape
    assert m % bm == 0 and n % bn == 0, (a.shape, block)
    grid = (m // bm, n // bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_fused_kernel, fmt=fmt, variant=variant),
        name="posit_fused_div_pallas",
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))


# =====================================================================
# rowwise: (rows, cols) / (rows, 1) with no materialized broadcast
# =====================================================================


def _rowwise_kernel(a_ref, b_ref, o_ref, *, fmt: PositFormat, variant: str):
    # The (bm, 1) divisor broadcasts through the datapath: quantize / decode
    # / didx / operand scaling happen once per row, the recurrence at full
    # block width.
    o_ref[...] = divide_floats_block(fmt, a_ref[...], b_ref[...], variant)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6))
def posit_fused_div_rowwise_pallas(
    fmt: PositFormat,
    a,
    b,
    block=(8, 256),
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = VMEM_LIMIT_BYTES,
    variant: str = DEFAULT_KERNEL_VARIANT,
):
    """Row-broadcast fused divider: ``a[(rows, cols)] / b[(rows, 1)]``.

    The divisor array stays ``(rows, 1)`` all the way into VMEM — each grid
    step sees a ``(bm, 1)`` divisor block, so divisor-side quantization and
    decode cost O(rows), not O(rows * cols), and no broadcast denominator is
    ever written to HBM.
    """
    assert a.ndim == 2 and b.shape == (a.shape[0], 1), (a.shape, b.shape)
    interpret = resolve_interpret(interpret)
    bm, bn = block
    m, n = a.shape
    assert m % bm == 0 and n % bn == 0, (a.shape, block)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_rowwise_kernel, fmt=fmt, variant=variant),
        name="posit_fused_div_rowwise_pallas",
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))


# =====================================================================
# softmax: max + exp + sum + SRT divide in one launch
# =====================================================================


def _softmax_kernel(x_ref, o_ref, *, fmt: PositFormat, variant: str,
                    cols_valid: int):
    x = x_ref[...]                                    # (bm, cols_pad)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < cols_valid
    m = jnp.max(jnp.where(valid, x, _NEG_HUGE), axis=-1, keepdims=True)
    # Padded lanes contribute exactly 0 to the row sum; the FIXED-ORDER
    # accumulation makes that an invariant rather than a hope: zeros are
    # additive identities at every partial sum, so the padded in-kernel
    # reduction is bit-identical to the emulate path's unpadded one for
    # every format (posit64 keeps all f32 mantissa bits — a free-order
    # jnp.sum here cost it 1 ulp of cross-backend agreement).
    e = jnp.where(valid, jnp.exp(x - m), 0.0)
    s = fixed_order_lanesum(e, cols_valid)            # (bm, 1)
    o_ref[...] = divide_floats_block(fmt, e, s, variant)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 6))
def posit_softmax_fused_pallas(
    fmt: PositFormat,
    x,
    cols_valid: int,
    block_rows: int = 8,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = VMEM_LIMIT_BYTES,
    variant: str = DEFAULT_KERNEL_VARIANT,
):
    """Single-launch posit softmax over complete rows.

    ``x`` is ``(rows, cols_pad)`` float32 with ``cols_valid <= cols_pad``
    real columns (the rest is padding, masked in-kernel).  Each grid step
    owns ``block_rows`` full rows, so the max/sum reductions never leave
    VMEM and the SRT divide consumes the ``(bm, 1)`` row sums directly.
    """
    assert x.ndim == 2
    interpret = resolve_interpret(interpret)
    m, n = x.shape
    bm = block_rows
    assert m % bm == 0, (x.shape, block_rows)
    assert 0 < cols_valid <= n, (cols_valid, n)
    return pl.pallas_call(
        functools.partial(_softmax_kernel, fmt=fmt, variant=variant,
                          cols_valid=cols_valid),
        name="posit_softmax_fused_pallas",
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
    )(x.astype(jnp.float32))
