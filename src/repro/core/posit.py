"""Bit-exact Posit<n,es> arithmetic primitives in JAX (vectorized).

Implements the 2022 Posit Standard encoding the paper adopts (es = 2, kept
parametric here): sign + run-length regime + up-to-es exponent bits + fraction,
two's-complement negatives, single NaR, no subnormals, round-to-nearest-even
on the integer body with saturation to minpos/maxpos (never to 0/NaR).

All functions operate on uint32 arrays holding n-bit patterns (n <= 32); the
Posit64 paths in :mod:`repro.core.divider` use :class:`BitVec` datapaths but
share this module's scalar field conventions.

Key encode property used throughout (and by the paper's Table III): once the
body ``regime||exp||frac`` is assembled as an (n-1)-bit integer, RNE rounding
is a plain integer increment — a carry out of the fraction correctly extends
into exponent and regime.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

_U32 = jnp.uint32
_I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class PositFormat:
    """Posit<n, es> format descriptor (standard posits have es=2)."""

    n: int
    es: int = 2

    def __post_init__(self):
        assert 3 <= self.n <= 32 or self.n == 64, self.n
        assert 0 <= self.es <= 4

    @property
    def F(self) -> int:
        """Maximum number of fraction bits (n - 3 - es; n-5 for es=2)."""
        return self.n - 3 - self.es

    @property
    def mask(self) -> int:
        return (1 << self.n) - 1

    @property
    def sign_bit(self) -> int:
        return 1 << (self.n - 1)

    @property
    def nar_pattern(self) -> int:
        return 1 << (self.n - 1)

    @property
    def maxpos_body(self) -> int:
        return (1 << (self.n - 1)) - 1

    @property
    def max_scale(self) -> int:
        """Scale of maxpos: (n-2) * 2**es."""
        return (self.n - 2) << self.es

    def __str__(self):
        return f"Posit{self.n}" if self.es == 2 else f"Posit<{self.n},{self.es}>"


POSIT8 = PositFormat(8)
POSIT16 = PositFormat(16)
POSIT32 = PositFormat(32)
# Wide format: patterns/significands exceed one uint32 word, so this module's
# u32 codecs do NOT apply — posit64 runs on the BitVec/word-tuple paths in
# :mod:`repro.core.wide` and :mod:`repro.kernels.posit_div`.
POSIT64 = PositFormat(64)


def _pow2_f32(e):
    """Exact 2^e for int32 e in [-126, 127], built from exponent bits."""
    return jax.lax.bitcast_convert_type(
        ((e.astype(_I32) + 127) << 23), jnp.float32)


def ldexp_f32(m, e):
    """``m * 2^e`` in float32 via two exact power-of-two factors.

    ``m`` is float32 or a signed integer below 2^24 (converted exactly);
    Mosaic has no unsigned->float cast, so callers holding uint32 values
    convert through int32.

    ``jnp.ldexp`` materializes 2^e as a single f32 factor, which is
    SUBNORMAL for e < -126 and gets flushed to zero on FTZ backends (XLA
    CPU) — so e.g. posit32 minpos-region values (true magnitude ~1e-36,
    comfortably NORMAL in f32) dequantized to 0.  Splitting e across two
    in-range factors keeps every intermediate normal whenever the final
    result is; only genuinely subnormal results remain at the mercy of the
    backend's flush mode (identically for every caller).
    """
    e = jnp.clip(e.astype(_I32), -252, 254)
    e1 = e >> 1           # arithmetic shift == floor(e / 2)
    return m.astype(jnp.float32) * _pow2_f32(e1) * _pow2_f32(e - e1)


def _bool_where(c, a, b):
    """``jnp.where`` on bool operands as logic: Mosaic cannot select i1."""
    return (c & a) | (~c & b)


def _safe_shl(x, s):
    """x << s with s possibly >= 32 (returns 0) — s is a traced array."""
    s = jnp.asarray(s)
    big = s >= 32
    return jnp.where(big, _U32(0), x << jnp.where(big, 0, s).astype(_U32))


def _safe_shr(x, s):
    s = jnp.asarray(s)
    big = s >= 32
    return jnp.where(big, _U32(0), x >> jnp.where(big, 0, s).astype(_U32))


# =====================================================================
# decode
# =====================================================================


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PositFields:
    """Decoded posit: value = (-1)^sign * 2^scale * sig / 2^F  (sig in [2^F, 2^{F+1}))."""

    sign: jnp.ndarray      # bool
    scale: jnp.ndarray     # int32, T = (k << es) + e
    sig: jnp.ndarray       # uint32, (1 << F) | frac
    is_zero: jnp.ndarray   # bool
    is_nar: jnp.ndarray    # bool

    def tree_flatten(self):
        return (self.sign, self.scale, self.sig, self.is_zero, self.is_nar), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def posit_decode(fmt: PositFormat, p) -> PositFields:
    """Decode n-bit posit patterns (uint32) into sign/scale/significand."""
    n, es, F = fmt.n, fmt.es, fmt.F
    p = p.astype(_U32) & _U32(fmt.mask)

    is_zero = p == 0
    is_nar = p == _U32(fmt.nar_pattern)

    # int compares, not bool casts: Mosaic cannot truncate to i1
    sign = ((p >> (n - 1)) & 1) == 1
    mag = jnp.where(sign, (~p + 1) & _U32(fmt.mask), p)

    # Left-align the (n-1)-bit body at bit 31.
    body = (mag << (32 - (n - 1))) & _U32(0xFFFFFFFF)
    r0 = (body >> 31) & 1
    inv = jnp.where(r0 == 1, ~body, body) & _U32(0xFFFFFFFF)
    run = jax.lax.clz(inv.astype(_I32)).astype(_I32)
    run = jnp.minimum(run, _I32(n - 1))  # regime may run to the end (no terminator)
    k = jnp.where(r0 == 1, run - 1, -run)

    # Bits past regime + terminator.
    tail = _safe_shl(body, (run + 1).astype(_U32))
    e = (tail >> (32 - es)).astype(_I32) if es > 0 else jnp.zeros_like(run)
    frac_tail = (tail << es) & _U32(0xFFFFFFFF) if es > 0 else tail
    frac = frac_tail >> (32 - F) if F > 0 else jnp.zeros_like(p)

    scale = (k << es) + e
    sig = (_U32(1 << F) | frac) if F > 0 else jnp.ones_like(p)
    return PositFields(sign=sign, scale=scale, sig=sig, is_zero=is_zero, is_nar=is_nar)


# =====================================================================
# encode
# =====================================================================


def posit_encode(
    fmt: PositFormat,
    sign,
    scale,
    frac,
    round_bit,
    sticky,
    is_zero,
    is_nar,
):
    """Assemble + RNE-round a posit from sign/scale/fraction and G/R/S info.

    ``frac`` is the F-bit fraction of a significand normalized to [1, 2);
    ``round_bit``/``sticky`` describe the discarded tail below the fraction.
    Saturates to maxpos/minpos (posit rounding never produces 0 or NaR from
    a nonzero real value).
    """
    n, es, F = fmt.n, fmt.es, fmt.F
    scale = scale.astype(_I32)
    frac = frac.astype(_U32)
    round_bit = round_bit.astype(_U32) & 1
    if sticky.dtype != jnp.bool_:
        sticky = sticky != 0

    k = scale >> es
    e = (scale & ((1 << es) - 1)).astype(_U32) if es > 0 else jnp.zeros_like(frac)

    over = k > (n - 2)
    under = k < -(n - 2)
    kc = jnp.clip(k, -(n - 2), n - 2)

    pos = kc >= 0
    l = jnp.where(pos, kc + 1, -kc)
    rlen = l + 1
    # Regime pattern, width rlen: l ones then 0  /  l zeros then 1.
    rpat = jnp.where(pos, (_safe_shl(jnp.full_like(frac, 1), l + 1) - 2), _U32(1))

    # eg = exponent || fraction, width F + es.
    eg = (e << F) | frac
    egw = F + es

    m = _I32(n - 1) - rlen  # bits available for eg; can be -1 when rlen == n
    m_pos = jnp.maximum(m, 0)
    discard = _I32(egw) - m_pos  # 0 .. egw

    kept = _safe_shr(eg, discard.astype(_U32))
    # Guard bit: first discarded bit (from eg, or incoming round bit if none).
    g_from_eg = _safe_shr(eg, jnp.maximum(discard - 1, 0).astype(_U32)) & 1
    guard = jnp.where(discard > 0, g_from_eg, round_bit)
    below_mask = _safe_shl(jnp.full_like(frac, 1), jnp.maximum(discard - 1, 0).astype(_U32)) - 1
    st_eg = (eg & below_mask) != 0
    sticky_full = _bool_where(discard > 0, st_eg | (round_bit != 0) | sticky,
                              sticky)

    # When m == -1 the regime itself is truncated: body = rpat >> 1; the value
    # is then >= the posit's scale ceiling and never rounds up (see below).
    trunc_regime = m < 0
    body_base = jnp.where(
        trunc_regime,
        rpat >> 1,
        _safe_shl(rpat, m_pos.astype(_U32)) | kept,
    )

    lsb = body_base & 1
    inc_linear = (guard & ((sticky_full).astype(_U32) | lsb)).astype(_U32)

    # --- non-linear (deep-regime) rounding -------------------------------
    # When the cut discards exponent bits (discard > F), adjacent posits
    # differ by a factor R = 2^(2^c) (c = discarded exponent bits) and
    # "nearest" must be judged on real values: round up iff
    #     2^e_disc * (1 + f) > (1 + R) / 2,
    # which for es = 2 reduces to:
    #     c = 1:  e_disc == 1  and  f > 1/4
    #     c = 2:  e_disc == 3  and  f > 1/16
    # with ties (exact equality) to even body.  f is compared exactly via
    # f_ext = frac . round . sticky as a (F+2)-bit fixed-point value.
    if es == 2 and F >= 2:
        c = discard - F
        f_ext = (frac << 2) | (round_bit << 1) | sticky.astype(_U32)
        e_disc1 = (e & 1) == 1
        e_disc2 = (e & 3) == 3
        thr = jnp.where(c == 1, _U32(1 << F), _U32(1 << (F - 2)))
        e_cond = _bool_where(c == 1, e_disc1, e_disc2)
        deep_up = e_cond & ((f_ext > thr) | ((f_ext == thr) & (lsb == 1)))
        deep = (c >= 1) & (m >= 0)
        inc = jnp.where(deep, deep_up.astype(_U32), inc_linear)
    else:
        inc = inc_linear
    inc = jnp.where(trunc_regime, _U32(0), inc)
    body = body_base + inc

    body = jnp.where(over, _U32(fmt.maxpos_body), body)
    body = jnp.where(under, _U32(1), body)
    # compare + select, not clip: Mosaic has no unsigned min/max
    body = jnp.where(body < 1, _U32(1), body)
    body = jnp.where(body > _U32(fmt.maxpos_body), _U32(fmt.maxpos_body), body)

    p = jnp.where(sign, (~body + 1) & _U32(fmt.mask), body)
    p = jnp.where(is_zero, _U32(0), p)
    p = jnp.where(is_nar, _U32(fmt.nar_pattern), p)
    return p.astype(_U32)


# =====================================================================
# float <-> posit casts (the quantization entry points)
# =====================================================================


def posit_to_float(fmt: PositFormat, p):
    """Posit bits -> float32. Exact for n <= 16; Posit32 rounds to f32."""
    d = posit_decode(fmt, p)
    # sig < 2^(F+1) <= 2^28: int32 holds it, and int32 -> f32 rounds the
    # same as the unsigned conversion would
    sigf = ldexp_f32(d.sig.astype(_I32).astype(jnp.float32), d.scale - fmt.F)
    val = jnp.where(d.sign, -sigf, sigf)
    val = jnp.where(d.is_zero, 0.0, val)
    val = jnp.where(d.is_nar, jnp.nan, val)
    return val


def float_decompose(x):
    """Exact integer decomposition of float32: (sign, scale, ti, is_zero, is_nar).

    ``ti`` is the 25-bit significand with the hidden bit at bit 24 (the low
    bit is 0 for normals), so the value is ``ti * 2^(scale - 24)``.  All
    classification and normalization run on the BIT FIELDS, never on float
    compares or ``frexp``: XLA flushes f32 subnormals in float comparisons
    (and ``frexp`` mis-normalizes them), and when a whole kernel body is
    compiled as one unit the optimizer can even rewrite a bitwise zero test
    back into a flushing float compare — integer field arithmetic is immune.
    Subnormals decompose exactly (clz-normalized), NaN and Inf both map to
    ``is_nar``.
    """
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    exp_f = ((bits >> 23) & _U32(0xFF)).astype(_I32)
    mant_f = bits & _U32(0x7FFFFF)
    is_sub = exp_f == 0
    is_zero = is_sub & (mant_f == 0)
    is_nar = exp_f == 255
    sign = ((bits >> 31) == 1) & ~is_zero
    blen = _I32(32) - jax.lax.clz(mant_f.astype(_I32))  # bitlength(mant_f)
    scale = jnp.where(is_sub, blen - 150, exp_f - 127)
    ti = jnp.where(is_sub,
                   mant_f << (_I32(25) - blen).astype(_U32),
                   (_U32(1 << 23) | mant_f) << 1)
    return sign, scale, ti, is_zero, is_nar


def float_to_posit(fmt: PositFormat, x):
    """float32 -> posit bits with correct RNE (via exact scaled integer)."""
    n, F = fmt.n, fmt.F
    sign, scale, ti, is_zero, is_nar = float_decompose(x)
    keep = F + 1                     # hidden bit + F fraction bits
    drop = 25 - keep
    if drop >= 1:
        frac = (ti >> drop) & _U32((1 << F) - 1)
        round_bit = (ti >> (drop - 1)) & 1
        sticky = (ti & _U32((1 << (drop - 1)) - 1)) != 0
    else:
        # F >= 24 (Posit32 from f32): no discarded bits.
        frac = (ti << (keep - 25)).astype(_U32) & _U32((1 << F) - 1)
        round_bit = jnp.zeros_like(ti)
        sticky = jnp.zeros_like(ti, dtype=jnp.bool_)

    return posit_encode(
        fmt, sign, scale, frac, round_bit, sticky, is_zero, is_nar
    )


# =====================================================================
# misc helpers
# =====================================================================


def posit_abs_lt(fmt: PositFormat, a, b):
    """|a| < |b| for posit patterns — monotone in the body integer."""
    da, db = posit_decode(fmt, a), posit_decode(fmt, b)
    mag_a = jnp.where(da.sign, (~a + 1) & _U32(fmt.mask), a)
    mag_b = jnp.where(db.sign, (~b + 1) & _U32(fmt.mask), b)
    return mag_a < mag_b


@functools.lru_cache(maxsize=None)
def format_for(n: int, es: int = 2) -> PositFormat:
    return PositFormat(n, es)
