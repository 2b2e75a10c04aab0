"""Quire: exact fused accumulation for posits (paper Section II-A).

Posit fused operations accumulate products into a wide fixed-point register
(the *quire*) and round once at the end.  This implements an exact quire for
Posit16: every posit16 x posit16 product bit is representable, so dot
products / MACs incur a single rounding — the property the paper credits for
posits' accuracy advantage (refs [4], [8]).

Width: products span weights 2^-134 .. 2^113 (scale range +-112, 2F = 22
fraction bits), so 248 value bits + sign + 32 carry-guard bits (> 2^31
accumulations) = 288 bits = 9 uint32 limbs.  (The 2022 standard quire16 is
256 bits with ulp 2^-112 — slightly *narrower* than exact for cross products
of tiny posits; we keep the exact variant and note the deviation.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bitvec import (
    BitVec,
    bv_add,
    bv_is_zero,
    bv_neg,
    bv_select,
    bv_shl_dyn,
    bv_shr_dyn,
    bv_sign,
    bv_to_u32,
    bv_zeros,
    bv_from_u32,
)
from .posit import PositFormat, posit_decode, posit_encode

_U32 = jnp.uint32
_I32 = jnp.int32

QUIRE_WIDTH = 288
_FRAC_OFF = 134  # bit position of weight 2^0


def quire_zero(like) -> BitVec:
    """Fresh quire register(s); ``like`` supplies the element shape."""
    return bv_zeros(QUIRE_WIDTH, jnp.zeros_like(like, dtype=_U32))


def quire_mac(fmt: PositFormat, q: BitVec, pa, pb) -> BitVec:
    """q += a * b exactly (posit16 patterns; NaR/zero handled)."""
    assert fmt.n <= 16, "exact quire implemented for n <= 16"
    da = posit_decode(fmt, pa)
    db = posit_decode(fmt, pb)
    F = fmt.F

    prod = (da.sig * db.sig).astype(_U32)            # <= 2F+2 bits, fits u32
    scale = da.scale + db.scale                      # value = prod/2^(2F) * 2^scale
    sign = da.sign ^ db.sign
    is_zero = da.is_zero | db.is_zero

    wide = bv_from_u32(prod, QUIRE_WIDTH)
    shift = (scale - 2 * F + _FRAC_OFF).astype(_I32)  # weight alignment
    term = bv_shl_dyn(wide, shift)
    term = bv_select(is_zero, quire_zero(bv_to_u32(q)), term)
    term = bv_select(sign & ~is_zero, bv_neg(term), term)
    return bv_add(q, term)


def quire_add_posit(fmt: PositFormat, q: BitVec, pa) -> BitVec:
    """q += a exactly (add a posit value, not a product)."""
    one = jnp.full_like(pa, 1 << (fmt.n - 2))  # posit 1.0 pattern
    return quire_mac(fmt, q, pa, one)


def _clz_wide(a: BitVec):
    from .wide import _clz

    return _clz(a)


@functools.partial(jax.jit, static_argnums=(0,))
def quire_to_posit(fmt: PositFormat, q: BitVec):
    """Round the quire to a posit (single rounding of the exact sum)."""
    F = fmt.F
    neg = bv_sign(q)
    mag = bv_select(neg, bv_neg(q), q)
    is_zero = bv_is_zero(mag)

    lz = _clz_wide(mag)                         # leading-zero count
    toppos = _I32(QUIRE_WIDTH - 1) - lz         # position of the leading 1
    scale = toppos - _FRAC_OFF

    # extract F+1 significand bits below (incl.) the leading one + G/S
    sh = toppos - F                             # bits below frac go to round/sticky
    kept = bv_select(sh >= 0,
                     bv_shr_dyn(mag, jnp.maximum(sh, 0)),
                     bv_shl_dyn(mag, jnp.maximum(-sh, 0)))
    frac = bv_to_u32(kept) & _U32((1 << F) - 1)
    rpos = jnp.maximum(sh - 1, 0)
    round_bit = jnp.where(sh >= 1, bv_to_u32(bv_shr_dyn(mag, rpos)) & 1, _U32(0))
    # sticky: any bit below the round bit
    below = bv_shl_dyn(mag, jnp.minimum(_I32(QUIRE_WIDTH) - rpos,
                                        _I32(QUIRE_WIDTH)) % _I32(QUIRE_WIDTH))
    sticky = jnp.where(rpos > 0, ~bv_is_zero(below), jnp.zeros_like(neg))

    return posit_encode(fmt, neg, scale, frac, round_bit, sticky,
                        is_zero, jnp.zeros_like(is_zero))


def fixed_order_rowsum(x, axis: int = -1, keepdims: bool = True):
    """Strictly sequential (left-to-right) float sum along ``axis``.

    ``jnp.sum``'s reduction ORDER is a compiler choice that varies with
    shape, padding and backend — which is exactly how the posit64 softmax
    picked up a 1-ulp emulate-vs-fused gap (the fused kernel reduced a
    padded tile, the emulate path an unpadded one, and the two trees
    grouped differently).  This helper pins the order to plain
    left-to-right accumulation: any two call sites that see the same
    values in the same lane order produce the same bits, and appended
    exact zeros are additive identities at every partial sum, so padded
    and unpadded rows agree bit-for-bit.

    This is the deterministic-order seam toward the quire: the exact
    accumulator above (:func:`fused_dot`) is order-INDEPENDENT, which is
    the end state; until a wide quire covers f32 attention/softmax rows,
    fixed order is the cheap contract that keeps every softmax backend
    bit-identical (posit64 included).
    """
    x = jnp.asarray(x)
    ax = axis % x.ndim
    xt = jnp.moveaxis(x, ax, 0)

    def body(j, acc):
        return acc + jax.lax.dynamic_index_in_dim(xt, j, 0, keepdims=False)

    acc = jax.lax.fori_loop(0, xt.shape[0], body,
                            jnp.zeros(xt.shape[1:], x.dtype))
    return jnp.expand_dims(acc, ax) if keepdims else acc


def fixed_order_lanesum(x, n: int):
    """:func:`fixed_order_rowsum` of the first ``n`` lanes of a 2-D tile.

    Kernel form of the same left-to-right accumulation: a static unrolled
    loop over lane slices, because Mosaic lowers no ``dynamic_slice`` of a
    value.  Lanes past ``n`` are not read; for zero padding that is the
    same sum, since zeros are additive identities at every partial sum.
    Returns ``(rows, 1)``.
    """
    acc = jnp.zeros((x.shape[0], 1), x.dtype)
    for j in range(n):
        acc = acc + x[:, j:j + 1]
    return acc


def fused_dot(fmt: PositFormat, pa, pb, axis: int = -1):
    """Exact posit dot product along ``axis`` with a single final rounding."""
    pa = jnp.moveaxis(pa.astype(_U32), axis, 0)
    pb = jnp.moveaxis(pb.astype(_U32), axis, 0)

    def body(q, ab):
        a, b = ab
        return quire_mac(fmt, q, a, b), None

    q0 = quire_zero(pa[0])
    q, _ = jax.lax.scan(body, q0, (pa, pb))
    return quire_to_posit(fmt, q)
