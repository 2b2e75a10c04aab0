"""Posit64 (and generic n>32) decode/encode/divide on BitVec datapaths.

The paper evaluates Posit16/32/64; Posit64's 60-bit significand exceeds a
uint32 word, so patterns, significands and the divider datapath run on
multi-limb BitVecs (2 limbs for the pattern, 3 for the widest scaled-radix-4
residual).  The divider recurrence itself is shared with
:mod:`repro.core.divider` (its datapath is width-generic); this module adds
the wide decode/encode with the same value-nearest deep-regime rounding as
the n<=32 path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bitvec import (
    BitVec,
    bv_add_bit,
    bv_and,
    bv_bit,
    bv_bit_dyn,
    bv_const,
    bv_eq,
    bv_from_u32,
    bv_is_zero,
    bv_mask,
    bv_neg,
    bv_or,
    bv_resize,
    bv_select,
    bv_shl,
    bv_shl_dyn,
    bv_shr,
    bv_shr_dyn,
    bv_sub,
    bv_to_u32,
    bv_ult,
    bv_zeros,
)
from .posit import PositFormat, float_decompose

_U32 = jnp.uint32
_I32 = jnp.int32


def _clz(bv: BitVec) -> jnp.ndarray:
    """Count leading zeros of the full width (int32)."""
    total = jnp.full_like(bv.limbs[0], bv.width, dtype=_I32)
    seen = jnp.zeros_like(bv.limbs[0], dtype=jnp.bool_)
    acc = jnp.zeros_like(bv.limbs[0], dtype=_I32)
    top_bits = bv.width - 32 * (len(bv.limbs) - 1)
    for i, limb in enumerate(reversed(bv.limbs)):
        width_here = top_bits if i == 0 else 32
        lz = jax.lax.clz(limb.astype(_I32)).astype(_I32) - (32 - width_here)
        here = limb != 0
        acc = jnp.where(~seen & here, acc + lz, acc)
        acc = jnp.where(~seen & ~here, acc + width_here, acc)
        seen = seen | here
    return jnp.minimum(acc, total)


def decode_wide(fmt: PositFormat, p: BitVec):
    """Decode n-bit posit patterns held in a BitVec (n up to 64).

    Returns (sign, scale, sig[BitVec width F+1], is_zero, is_nar).
    """
    n, es, F = fmt.n, fmt.es, fmt.F
    assert p.width == n
    is_zero = bv_is_zero(p)
    nar = bv_const(1 << (n - 1), n, bv_to_u32(p))
    is_nar = bv_eq(p, nar)

    sign = bv_bit_dyn(p, jnp.int32(n - 1)) == 1
    mag = bv_select(sign, bv_neg(p), p)

    body = bv_shl(mag, 1)  # n-1 bits left-aligned at bit n-1
    r0 = bv_bit_dyn(body, jnp.int32(n - 1)) == 1
    inv = bv_select(r0, bv_mask(BitVec([~l for l in body.limbs], n)), body)
    # leading-run length over bits n-1 .. 1
    run = jnp.minimum(_clz(inv), _I32(n - 1))
    k = jnp.where(r0, run - 1, -run)

    tail = bv_shl_dyn(body, (run + 1).astype(_I32))
    e = bv_to_u32(bv_shr(tail, n - es)).astype(_I32) if es else jnp.zeros_like(run)
    frac_tail = bv_shl(tail, es)
    sig = bv_shr(frac_tail, n - F)          # F bits, left-aligned fraction
    sig = bv_resize(sig, F + 1)
    one = bv_shl(bv_from_u32(jnp.ones_like(bv_to_u32(p)), F + 1), F)
    sig = bv_or(sig, one)                   # hidden bit

    scale = (k << es) + e
    return sign, scale, sig, is_zero, is_nar


def encode_wide(fmt: PositFormat, sign, scale, frac: BitVec, round_bit, sticky,
                is_zero, is_nar) -> BitVec:
    """Assemble + round an n-bit posit (value-nearest, saturating)."""
    n, es, F = fmt.n, fmt.es, fmt.F
    like = bv_to_u32(frac)
    scale = scale.astype(_I32)
    round_bit = round_bit.astype(_U32) & 1
    if sticky.dtype != jnp.bool_:
        sticky = sticky != 0

    k = scale >> es
    e = (scale & ((1 << es) - 1)).astype(_U32)
    over = k > (n - 2)
    under = k < -(n - 2)
    kc = jnp.clip(k, -(n - 2), n - 2)

    pos = kc >= 0
    l = jnp.where(pos, kc + 1, -kc)
    rlen = l + 1
    ones = bv_from_u32(jnp.ones_like(like), n)
    # regime pattern: pos -> (2^l - 1) << 1 ; neg -> 1
    rpat_pos = bv_sub(bv_shl_dyn(ones, (l + 1).astype(_I32)), bv_const(2, n, like))
    rpat = bv_select(pos, rpat_pos, bv_const(1, n, like))

    egw = F + es
    eg = bv_or(bv_shl(bv_resize(bv_from_u32(e, 32), egw), F), bv_resize(frac, egw))
    m = _I32(n - 1) - rlen
    m_pos = jnp.maximum(m, 0)
    discard = _I32(egw) - m_pos

    kept = bv_shr_dyn(bv_resize(eg, n), discard)
    g_from_eg = bv_bit_dyn(bv_resize(eg, n), jnp.maximum(discard - 1, 0))
    guard = jnp.where(discard > 0, g_from_eg, round_bit)
    below = bv_sub(bv_shl_dyn(ones, jnp.maximum(discard - 1, 0).astype(_I32)),
                   bv_const(1, n, like))
    st_eg = ~bv_is_zero(bv_and(bv_resize(eg, n), below))
    sticky_full = jnp.where(discard > 0,
                            st_eg | (round_bit != 0) | sticky, sticky)

    trunc_regime = m < 0
    body_base = bv_select(
        trunc_regime, bv_shr(rpat, 1),
        bv_or(bv_shl_dyn(rpat, m_pos.astype(_I32)), kept))

    lsb = bv_bit_dyn(body_base, jnp.int32(0))
    inc_linear = (guard & (sticky_full.astype(_U32) | lsb)).astype(_U32)

    # value-nearest deep-regime rounding (c discarded exponent bits)
    c = discard - F
    f_ext = bv_or(bv_shl(bv_resize(frac, F + 2), 2),
                  bv_from_u32((round_bit << 1) | sticky.astype(_U32), F + 2))
    thr1 = bv_const(1 << F, F + 2, like)
    thr2 = bv_const(1 << (F - 2), F + 2, like)
    thr = bv_select(c == 1, thr1, thr2)
    e_cond = jnp.where(c == 1, (e & 1) == 1, (e & 3) == 3)
    f_gt = bv_ult(thr, f_ext)
    f_tie = bv_eq(f_ext, thr)
    deep_up = e_cond & (f_gt | (f_tie & (lsb == 1)))
    deep = (c >= 1) & (m >= 0)
    inc = jnp.where(deep, deep_up.astype(_U32), inc_linear)
    inc = jnp.where(trunc_regime, _U32(0), inc)

    body = bv_add_bit(body_base, inc)
    maxpos = bv_const((1 << (n - 1)) - 1, n, like)
    one_bv = bv_const(1, n, like)
    body = bv_select(over | bv_ult(maxpos, body), maxpos, body)
    body = bv_select(under | bv_is_zero(body), one_bv, body)

    out = bv_select(sign, bv_neg(body), body)
    out = bv_select(is_zero, bv_zeros(n, like), out)
    out = bv_select(is_nar, bv_const(1 << (n - 1), n, like), out)
    return out


# ---------------------------------------------------------------------------
# float32 <-> wide-posit casts (the quantization entry points for n > 32)
# ---------------------------------------------------------------------------


def float_to_posit_wide(fmt: PositFormat, x) -> BitVec:
    """float32 -> n-bit posit patterns as a BitVec (n > 32).

    Every finite float32 value is exactly representable in posit64 except
    deep in the regime tails, where ``encode_wide`` applies the standard
    value-nearest rounding — so this is the correct RNE quantization for the
    whole f32 range, used identically by the emulate backend and (inside the
    kernel body) by the fused wide datapath.
    """
    n, F = fmt.n, fmt.F
    assert n > 32 and F >= 24, fmt
    # Integer-only f32 decomposition (see posit.float_decompose): subnormals
    # normalize exactly and none of the classification can be rewritten into
    # a flushing float compare when a kernel body compiles as one unit.
    sign, scale, ti, is_zero, is_nar = float_decompose(x)
    frac = bv_shl(bv_from_u32(ti & _U32((1 << 24) - 1), F), F - 24)
    zero = jnp.zeros_like(ti)
    return encode_wide(fmt, sign, scale, frac, zero,
                       jnp.zeros_like(is_zero), is_zero, is_nar)


def posit_wide_to_float(fmt: PositFormat, p: BitVec):
    """n-bit posit patterns (BitVec) -> float32 with RNE to 24 bits.

    The G/R/S extraction on the wide significand is exact; the final
    scaling (``ldexp_f32``) multiplies an exactly-representable 24-bit
    integer by two exact power-of-two factors, so normal-range outputs are
    correctly rounded (subnormal outputs inherit the backend's flush mode,
    identically for the emulate and fused paths).
    """
    from .posit import ldexp_f32

    F = fmt.F
    sign, scale, sig, is_zero, is_nar = decode_wide(fmt, p)
    if F + 1 > 24:
        sh = F + 1 - 24  # discarded low bits of the wide significand
        m24 = bv_to_u32(bv_shr(sig, sh))
        guard = bv_bit(sig, sh - 1)
        low = bv_and(sig, bv_const((1 << (sh - 1)) - 1, sig.width,
                                   bv_to_u32(sig)))
        sticky = (~bv_is_zero(low)).astype(_U32)
        m24 = m24 + (guard & (sticky | (m24 & 1)))
        val = ldexp_f32(m24.astype(_I32), scale - 23)
    else:
        val = ldexp_f32(bv_to_u32(sig).astype(_I32), scale - F)
    val = jnp.where(sign, -val, val)
    val = jnp.where(is_zero, 0.0, val)
    return jnp.where(is_nar, jnp.nan, val)


@functools.partial(jax.jit, static_argnums=(0, 3))
def posit_divide_wide(fmt: PositFormat, px: BitVec, pd: BitVec,
                      variant: str = "srt_r4_cs_of_fr") -> BitVec:
    """Bit-exact posit division for wide formats (Posit64) on BitVec patterns."""
    from .divider import VARIANTS, _fraction_divide

    cfg = VARIANTS[variant]
    sx, Tx, sigx, zx, nx = decode_wide(fmt, px)
    sd, Td, sigd, zd, nd = decode_wide(fmt, pd)

    sign = sx ^ sd
    scale = Tx - Td

    frac, t_adj, round_bit, sticky, _ = _fraction_divide(fmt, cfg, sigx, sigd)

    out_nar = nx | nd | zd
    out_zero = zx & ~out_nar
    return encode_wide(fmt, sign, scale + t_adj, frac, round_bit, sticky,
                       out_zero, out_nar)
