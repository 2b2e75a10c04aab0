"""Pallas TPU kernels: float32 <-> posit quantize/dequantize.

These are the wire/storage-format casts used by the numerics layer (posit
activations / gradient compression / KV-cache quantization).  Elementwise,
VMEM-tiled; the heavy lifting (regime encode with RNE, clz-based decode) is
shared with the exhaustively-validated :mod:`repro.core.posit`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.posit import PositFormat, float_to_posit, posit_to_float
from .posit_div import VMEM_LIMIT_BYTES, resolve_interpret

_U32 = jnp.uint32


def _quant_kernel(x_ref, o_ref, *, fmt: PositFormat):
    o_ref[...] = float_to_posit(fmt, x_ref[...])


def _dequant_kernel(p_ref, o_ref, *, fmt: PositFormat):
    o_ref[...] = posit_to_float(fmt, p_ref[...])


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def posit_quantize_pallas(fmt: PositFormat, x, block=(64, 256),
                          interpret: Optional[bool] = None,
                          vmem_limit_bytes: int = VMEM_LIMIT_BYTES):
    assert x.ndim == 2
    interpret = resolve_interpret(interpret)
    bm, bn = block
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_quant_kernel, fmt=fmt),
        name="posit_quantize_pallas",
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
        grid=(m // bm, n // bn),
        in_specs=[spec],
        out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(x.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def posit_dequantize_pallas(fmt: PositFormat, p, block=(64, 256),
                            interpret: Optional[bool] = None,
                            vmem_limit_bytes: int = VMEM_LIMIT_BYTES):
    assert p.ndim == 2
    interpret = resolve_interpret(interpret)
    bm, bn = block
    m, n = p.shape
    assert m % bm == 0 and n % bn == 0
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_dequant_kernel, fmt=fmt),
        name="posit_dequantize_pallas",
        out_shape=jax.ShapeDtypeStruct(p.shape, jnp.float32),
        grid=(m // bm, n // bn),
        in_specs=[spec],
        out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(p.astype(_U32))
