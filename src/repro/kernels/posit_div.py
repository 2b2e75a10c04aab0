"""Pallas TPU kernel: batched posit division (SRT digit recurrence).

TPU adaptation of the paper's Table IV dividers: each 8x128 vector lane is
one divider instance, the residual lives in VREGs across all iterations, and
quotient-digit selection is a branchless compare ladder on a truncated
estimate of the TOP residual word.

The datapath is parameterized by a :class:`DatapathPlan`: the residual is a
W-word (W in {1, 2}) little-endian int32 register (a carry-save PAIR of them
for the redundant variants) with ``_IB = 3`` integer bits at the top of the
top word and ``32*W - 3`` fraction bits below.  :func:`kernel_datapath_plan`
picks the narrowest W that holds the operand fraction (plus 3 extra bits for
the scaled variant's Table I multiples), so every Table IV row lowers for
every format whose fraction fits the two-word frame — in particular
``srt_r4_scaled`` for ALL n <= 32 and posit64 (two-word significand) for
every unscaled variant.  Cross-word carry propagation is confined to

  * the CSA carry word's ``<< 1`` (one OR into the next word per iteration),
  * the full ripple adds of the non-redundant variants and of termination,

while the digit-selection estimate reads the TOP WORD only (the paper's
truncated-estimate selection, Section III-D), so selection cost does not
grow with W.

Variant coverage mirrors ``core.divider.VARIANTS`` (all Table IV rows); the
feature flags — radix, redundant (carry-save) residual, on-the-fly quotient
conversion, operand scaling, nonrestoring — are taken from the same
:class:`~repro.core.divider.DividerConfig` rows, and ``core/divider.py``
stays the bit-exact golden oracle for all of them.

Datapath trick (vs. the generic BitVec emulation): residuals are kept on the
operand grid by folding the w(0) = x/p initialization into the first
iteration — y_1 = p*w(0) = x exactly (p = the radix) — so the iteration
count drops by one and operands left-align directly under the binary point.

Entry points:

  * :func:`posit_div_pallas`     — uint32 bit-pattern arrays (n <= 32 only;
    wide patterns do not fit one u32 word).
  * :func:`divide_floats_block`  — float32 -> quantize -> divide ->
    dequantize on one block, for ANY planned format including posit64; this
    is the primitive the fused kernels and the flash-attention normalizer
    compose.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import seltables
from repro.core.divider import VARIANTS as _TABLE4
from repro.core.posit import (
    PositFormat,
    float_to_posit,
    posit_decode,
    posit_encode,
    posit_to_float,
)

_U32 = jnp.uint32
_I32 = jnp.int32

_IB = 3         # residual integer bits (incl. sign) at the top of the frame
_WPOINT = 29    # fraction bits held by the TOP residual word (32 - _IB)
_MAX_WORDS = 2  # widest residual frame: two words, 61 fraction bits

# Scoped-VMEM budgets the kernels request; v5e has 128 MiB of VMEM per
# core.  Elementwise tiles need well under a MiB.  The flash kernels hold a
# whole (Sk, 128) f32 K and V per grid step, double-buffered: 4 MiB at
# Sk = 2048, so 64 MiB covers contexts to about 16K.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
FLASH_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

# Exported for the static prover (repro.analysis.datapath): the W-word
# residual frame holds values in [-2^(_IB-1), 2^(_IB-1)) with 32*W - _IB
# fraction bits; the prover shows every reachable residual/divisor multiple
# stays strictly inside that window for every accepted plan.
RESIDUAL_INT_BITS = _IB
MAX_RESIDUAL_WORDS = _MAX_WORDS

# Table IV rows with an in-register W-word Pallas datapath (all of them).
KERNEL_VARIANTS = tuple(_TABLE4)
DEFAULT_KERNEL_VARIANT = "srt_r4_cs_of_fr"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` auto-selects: interpret off-TPU, compiled on TPU."""
    return not on_tpu() if interpret is None else interpret


# =====================================================================
# datapath plan
# =====================================================================


@dataclasses.dataclass(frozen=True)
class DatapathPlan:
    """Static lowering plan for one (format, variant) divider instance."""

    variant: str
    n: int
    words: int          # W: residual words (per carry-save register)
    radix: int
    redundant: bool     # carry-save residual pair (vs full two's-comp add)
    otf: bool           # on-the-fly conversion (vs plain accumulate + Q-1)
    nonrestoring: bool  # Algorithm 1: digit set {-1, 1}, sign-only select
    scaled: bool        # operand scaling (Table I / Eq 29)
    frac: int           # FRAC = F + 1 operand fraction bits
    shift: int          # left-align shift of the significand into the frame
    iterations: int     # after folding the first iteration into init
    fp: int             # quotient fraction bits
    qwords: int         # words per quotient register
    gbits: int          # estimate fraction bits (estimate is _IB + gbits)

    @property
    def wf(self) -> int:
        """Total fraction bits under the residual binary point."""
        return 32 * self.words - _IB


@functools.lru_cache(maxsize=None)
def kernel_datapath_plan(fmt: PositFormat, variant: str) -> Optional[DatapathPlan]:
    """The W-word datapath plan for (fmt, variant), or None if unplannable.

    The scaled variant's operands carry FRAC + 3 fraction bits (Table I
    multiples) and need 3 bits of exact-shift headroom; unscaled variants
    need 1.  The narrowest W in {1, .., _MAX_WORDS} whose ``32*W - 3``
    fraction bits cover that is chosen: n <= 30 keeps the original
    single-word plan, posit31/32-scaled and posit64 go two-word.
    """
    cfg = _TABLE4.get(variant)
    if cfg is None:
        return None
    frac = fmt.F + 1
    margin = 3 if cfg.scaling else 1
    words = next((w for w in range(1, _MAX_WORDS + 1)
                  if frac + margin <= 32 * w - _IB), None)
    if words is None:
        return None
    lr = cfg.log2r
    it = -(-(fmt.n - 1) // lr)  # Eq 31 with h = n - 1 quotient bits
    fp = it * lr - lr           # first iteration folded: p_shift == log2(r)
    if cfg.radix == 2 or not cfg.redundant_residual:
        gbits = 1               # tb = 4: 3 int + 1 frac (Eqs 26-27)
    elif cfg.scaling:
        gbits = seltables.SCALED_G_FRAC
    else:
        gbits = seltables.G_FRAC
    return DatapathPlan(
        variant=variant, n=fmt.n, words=words, radix=cfg.radix,
        redundant=cfg.redundant_residual, otf=cfg.otf,
        nonrestoring=cfg.nonrestoring, scaled=cfg.scaling, frac=frac,
        shift=32 * words - _IB - frac, iterations=it, fp=fp,
        qwords=-(-(fp + 2) // 32), gbits=gbits)


def kernel_variant_supported(fmt: PositFormat, variant: str) -> bool:
    """Can (fmt, variant) run on the in-register W-word datapath?"""
    return kernel_datapath_plan(fmt, variant) is not None


def planned_pairs(formats=None):
    """Every ``(fmt, variant, plan)`` the kernel datapath accepts.

    ``formats`` defaults to the full registered set (posit8/16/32/64).
    This is the iteration surface of the static prover: each yielded plan
    must be PROVEN (containment, residual width, scaling range, OTF width)
    by ``python -m repro.analysis``.
    """
    if formats is None:
        from repro.numerics.formats import NUMERIC_FORMATS

        formats = tuple(NUMERIC_FORMATS.values())
    for fmt in formats:
        for variant in KERNEL_VARIANTS:
            plan = kernel_datapath_plan(fmt, variant)
            if plan is not None:
                yield fmt, variant, plan


def kernel_plan_error(fmt: PositFormat, variant: str) -> Optional[str]:
    """None if (fmt, variant) has a datapath plan, else the derived reason."""
    if variant not in _TABLE4:
        return (f"unknown divider variant {variant!r}; Table IV rows: "
                f"{KERNEL_VARIANTS}")
    if kernel_datapath_plan(fmt, variant) is not None:
        return None
    cfg = _TABLE4[variant]
    margin = 3 if cfg.scaling else 1
    max_n = (32 * _MAX_WORDS - _IB - margin) + 2 + fmt.es  # FRAC = n - 2 - es
    return (f"{fmt} / {variant!r} needs {fmt.F + 1 + margin} residual "
            f"fraction bits but the widest ({_MAX_WORDS}-word) frame holds "
            f"{32 * _MAX_WORDS - _IB}; {variant!r} supports n <= {max_n}"
            + (" (operand scaling carries 3 extra fraction bits)"
               if cfg.scaling else ""))


# =====================================================================
# W-word register helpers (little-endian tuples of int32 arrays)
# =====================================================================


def _lsr(x, k: int):
    """Logical right shift of one int32 word by a static amount."""
    if k == 0:
        return x
    if k >= 32:
        return jnp.zeros_like(x)
    return (x.astype(_U32) >> _U32(k)).astype(_I32)


def _w_shl(w: Tuple, k: int) -> Tuple:
    """Static left shift; bits cross word boundaries upward."""
    ls, bs = divmod(k, 32)
    out = []
    for i in range(len(w)):
        j = i - ls
        if j < 0:
            out.append(jnp.zeros_like(w[0]))
            continue
        cur = w[j] << bs if bs else w[j]
        if bs and j >= 1:
            cur = cur | _lsr(w[j - 1], 32 - bs)
        out.append(cur)
    return tuple(out)


def _w_shr(w: Tuple, k: int) -> Tuple:
    """Static LOGICAL right shift; bits cross word boundaries downward."""
    ls, bs = divmod(k, 32)
    out = []
    for i in range(len(w)):
        j = i + ls
        if j >= len(w):
            out.append(jnp.zeros_like(w[0]))
            continue
        cur = _lsr(w[j], bs)
        if bs and j + 1 < len(w):
            cur = cur | (w[j + 1] << (32 - bs))
        out.append(cur)
    return tuple(out)


def _w_add(a: Tuple, b: Tuple, cin=None) -> Tuple:
    """Full W-word add (ripple carry); ``cin`` is an optional 0/1 int32."""
    out = []
    carry = cin
    for x, y in zip(a, b):
        xu, yu = x.astype(_U32), y.astype(_U32)
        s = xu + yu
        c = (s < xu).astype(_U32)
        if carry is not None:
            s2 = s + carry.astype(_U32)
            c = c | (s2 < s).astype(_U32)
            s = s2
        out.append(s.astype(_I32))
        carry = c
    return tuple(out)


def _w_csa(a: Tuple, b: Tuple, c: Tuple, cin) -> Tuple:
    """3:2 carry-save step: per-word full adders, carries shift one left."""
    s = tuple(x ^ y ^ z for x, y, z in zip(a, b, c))
    maj = tuple((x & y) | (x & z) | (y & z) for x, y, z in zip(a, b, c))
    carry = _w_shl(maj, 1)
    return s, (carry[0] | cin,) + carry[1:]


def _w_not(w: Tuple) -> Tuple:
    return tuple(~x for x in w)


def _w_sel(cond, a: Tuple, b: Tuple) -> Tuple:
    return tuple(jnp.where(cond, x, y) for x, y in zip(a, b))


def _w_sub1(w: Tuple) -> Tuple:
    """w - 1 (adds the all-ones pattern)."""
    return _w_add(w, tuple(jnp.full_like(x, -1) for x in w))


def _w_bit(w: Tuple, pos: int):
    return _lsr(w[pos // 32], pos % 32) & _I32(1)


def _w_nonzero(w: Tuple):
    acc = w[0]
    for x in w[1:]:
        acc = acc | x
    return acc != 0


def _w_low_nonzero(w: Tuple, nbits: int):
    """(w mod 2^nbits) != 0."""
    acc = None
    for i, x in enumerate(w):
        lo = 32 * i
        if nbits <= lo:
            break
        word = x if nbits >= lo + 32 else x & _I32((1 << (nbits - lo)) - 1)
        acc = word if acc is None else acc | word
    if acc is None:
        return jnp.zeros_like(w[0], dtype=jnp.bool_)
    return acc != 0


def _w_mask(w: Tuple, nbits: int) -> Tuple:
    """Keep the low ``nbits`` bits."""
    out = []
    for i, x in enumerate(w):
        lo = 32 * i
        if nbits <= lo:
            out.append(jnp.zeros_like(x))
        elif nbits >= lo + 32:
            out.append(x)
        else:
            out.append(x & _I32((1 << (nbits - lo)) - 1))
    return tuple(out)


# =====================================================================
# quotient-digit selection (Section III-D) — top residual word only
# =====================================================================


def _lut8(table, idx):
    """8-entry lookup as a compare ladder of scalar constants (VMEM-free)."""
    out = jnp.full_like(idx, table[7])
    for i in range(7):
        out = jnp.where(idx == i, _I32(table[i]), out)
    return out


def _sel_r4(est, didx):
    """Radix-4 selection (Eq 28): est in units of 1/16, didx in [0, 8)."""
    m2 = _lut8(seltables.RADIX4_M2, didx)
    m1 = _lut8(seltables.RADIX4_M1, didx)
    m0 = _lut8(seltables.RADIX4_M0, didx)
    mm1 = _lut8(seltables.RADIX4_MM1, didx)
    return jnp.where(
        est >= m2, _I32(2),
        jnp.where(est >= m1, _I32(1),
                  jnp.where(est >= m0, _I32(0),
                            jnp.where(est >= mm1, _I32(-1), _I32(-2)))))


def _sel_r2(est):
    """Radix-2 CS selection (Eq 27): est in units of 1/2 (4-bit estimate)."""
    return jnp.where(est >= seltables.R2_CS_M1, _I32(1),
                     jnp.where(est == seltables.R2_CS_M0, _I32(0), _I32(-1)))


def _sel_r2_exact(est):
    """Radix-2 non-redundant selection (Eq 26): est = floor(2w) in halves."""
    return jnp.where(est >= seltables.R2_EXACT_M1, _I32(1),
                     jnp.where(est >= seltables.R2_EXACT_M0, _I32(0),
                               _I32(-1)))


def _sel_r4_scaled(est):
    """Scaled radix-4 selection (Eq 29): divisor-independent, units of 1/8."""
    return jnp.where(
        est >= seltables.SCALED_M2, _I32(2),
        jnp.where(est >= seltables.SCALED_M1, _I32(1),
                  jnp.where(est >= seltables.SCALED_M0, _I32(0),
                            jnp.where(est >= seltables.SCALED_MM1, _I32(-1),
                                      _I32(-2)))))


def _cs_est(rws_top, rwc_top, gbits):
    """Truncated estimate from the TOP words: 3 int + ``gbits`` frac bits."""
    tb = _IB + gbits
    sh = _WPOINT - gbits
    t = ((rws_top >> sh) + (rwc_top >> sh)) & _I32((1 << tb) - 1)
    return (t << (32 - tb)) >> (32 - tb)  # sign-extend tb bits


# Operand scaling (Table I): v -> v + (v >> s1) + (v >> s2), selected by the
# 3 top fraction bits of d.  s2 == 0 encodes "no third term".
_SCALE_S1 = tuple(s[0] for s in seltables.SCALING_SHIFTS)
_SCALE_S2 = tuple(0 if s[1] is None else s[1] for s in seltables.SCALING_SHIFTS)


def _scale_operand(v: Tuple, didx) -> Tuple:
    """Exact W-word M*v (the aligned operand has >= 3 trailing zero bits)."""
    c1, c2, c3 = _w_shr(v, 1), _w_shr(v, 2), _w_shr(v, 3)
    s1 = _lut8(_SCALE_S1, didx)
    s2 = _lut8(_SCALE_S2, didx)
    zero = tuple(jnp.zeros_like(x) for x in v)
    t1 = _w_sel(s1 == 1, c1, _w_sel(s1 == 2, c2, c3))
    t2 = _w_sel(s2 == 1, c1, _w_sel(s2 == 3, c3, zero))
    return _w_add(_w_add(v, t1), t2)


# =====================================================================
# quotient registers (on-the-fly conversion or plain accumulation)
# =====================================================================


def _otf(Q: Tuple, QD: Tuple, digit, r: int) -> Tuple:
    """On-the-fly conversion step (Eqs 18-19), radix r in {2, 4}."""
    lr = 1 if r == 2 else 2
    neg = digit < 0
    pos = digit > 0
    mag = jnp.abs(digit)
    Qs, QDs = _w_shl(Q, lr), _w_shl(QD, lr)
    q_app = jnp.where(neg, _I32(r) - mag, mag)
    qd_app = jnp.where(pos, mag - 1, _I32(r - 1) - mag)
    Qn = _w_sel(neg, QDs, Qs)
    QDn = _w_sel(pos, Qs, QDs)
    return (Qn[0] | q_app,) + Qn[1:], (QDn[0] | qd_app,) + QDn[1:]


def _plain_q(Q: Tuple, digit, r: int) -> Tuple:
    """Non-OTF accumulation q <- r*q + digit (digit may be negative)."""
    lr = 1 if r == 2 else 2
    Qs = _w_shl(Q, lr)
    mag = jnp.abs(digit)
    magw = (mag,) + tuple(jnp.zeros_like(mag) for _ in Q[1:])
    neg = digit < 0
    return _w_add(Qs, _w_sel(neg, _w_not(magw), magw), neg.astype(_I32))


# =====================================================================
# the recurrence on decoded significands
# =====================================================================


def _divide_fields(plan: DatapathPlan, xsig: Tuple, dsig: Tuple):
    """Run the W-word digit recurrence on significand word tuples.

    ``xsig``/``dsig`` are little-endian int32 word tuples holding FRAC-bit
    significands (values in [2^(FRAC-1), 2^FRAC), i.e. fractions in
    [1/2, 1)).  ``dsig`` may broadcast against ``xsig`` (a per-row divisor);
    every divisor-side quantity is then computed once per row.  Returns
    (frac_words, t_adj, round_bit, sticky) like ``divider._fraction_divide``.
    """
    W, r = plan.words, plan.radix
    lr = 1 if r == 2 else 2
    FRAC, It, FP = plan.frac, plan.iterations, plan.fp
    F = FRAC - 1

    def extend(sig):
        return sig + tuple(jnp.zeros_like(sig[0]) for _ in range(W - len(sig)))

    x_al = _w_shl(extend(xsig), plan.shift)
    d_al = _w_shl(extend(dsig), plan.shift)
    if FRAC >= 4:
        didx = _w_shr(dsig, FRAC - 4)[0] & _I32(7)
    else:
        didx = (dsig[0] << (4 - FRAC)) & _I32(7)
    if plan.scaled:
        # Both operands times the same M (Table I): the quotient is
        # unchanged, the divisor lands in [1 - 1/64, 1 + 1/8] so selection
        # constants become divisor-independent.  Exact: shift >= 3
        # guarantees no bits fall off the bottom.
        x_al = _scale_operand(x_al, didx)
        d_al = _scale_operand(d_al, didx)
    d2 = _w_shl(d_al, 1) if r == 4 else None

    def select(rws_top, rwc_top):
        if plan.nonrestoring:
            return jnp.where(rws_top < 0, _I32(-1), _I32(1))
        est = _cs_est(rws_top, rwc_top, plan.gbits)
        if not plan.redundant:
            return _sel_r2_exact(est)
        if r == 2:
            return _sel_r2(est)
        if plan.scaled:
            return _sel_r4_scaled(est)
        return _sel_r4(est, didx)

    def addend_for(digit):
        add = []
        for i in range(W):
            a = jnp.where(digit == 1, ~d_al[i],
                          jnp.where(digit == -1, d_al[i], _I32(0)))
            if r == 4:
                a = jnp.where(digit == 2, ~d2[i],
                              jnp.where(digit == -2, d2[i], a))
            add.append(a)
        return tuple(add), (digit > 0).astype(_I32)

    # Iteration 1 folded: y_1 = r*w(0) = x exactly (w(0) = x/r).
    ztop = jnp.zeros_like(x_al[-1])
    digit = select(x_al[-1], ztop)
    add, cin = addend_for(digit)
    if plan.redundant:
        wc = _w_shl(tuple(x & a for x, a in zip(x_al, add)), 1)
        ws = tuple(x ^ a for x, a in zip(x_al, add))
        wc = (wc[0] | cin,) + wc[1:]
    else:
        ws = _w_add(x_al, add, cin)
        wc = tuple(jnp.zeros_like(x) for x in ws)
    qz = tuple(jnp.zeros_like(digit) for _ in range(plan.qwords))
    if plan.otf:
        Q, QD = _otf(qz, qz, digit, r)
    else:
        Q, QD = _plain_q(qz, digit, r), qz

    def body(_, carry):
        ws, wc, Q, QD = carry
        rws = _w_shl(ws, lr)
        if plan.redundant:
            rwc = _w_shl(wc, lr)
            digit = select(rws[-1], rwc[-1])
            add, cin = addend_for(digit)
            ws_n, wc_n = _w_csa(rws, rwc, add, cin)
        else:
            digit = select(rws[-1], ztop)
            add, cin = addend_for(digit)
            ws_n, wc_n = _w_add(rws, add, cin), wc
        if plan.otf:
            Qn, QDn = _otf(Q, QD, digit, r)
        else:
            Qn, QDn = _plain_q(Q, digit, r), QD
        return ws_n, wc_n, Qn, QDn

    ws, wc, Q, QD = jax.lax.fori_loop(0, It - 1, body, (ws, wc, Q, QD))

    # Termination: sign/zero of the final residual (the FR lookahead in HW).
    wfull = _w_add(ws, wc) if plan.redundant else ws
    neg = wfull[-1] < 0
    if not plan.otf:
        QD = _w_sub1(Q)
    qf = _w_sel(neg, QD, Q)
    rem = _w_sel(neg, _w_add(wfull, d_al), wfull)
    rem_nz = _w_nonzero(rem)

    # q = qf * 2^-FP in (1/2, 2); normalize and extract F + G/R/S bits.
    intbit = _w_bit(qf, FP) == 1
    qn = _w_sel(intbit, qf, _w_shl(qf, 1))
    t_adj = jnp.where(intbit, _I32(0), _I32(-1))
    frac = _w_mask(_w_shr(qn, FP - F), F)
    round_bit = _w_bit(qn, FP - F - 1)
    sticky = _w_low_nonzero(qn, FP - F - 1) | rem_nz
    return frac, t_adj, round_bit, sticky


# =====================================================================
# block-level dividers
# =====================================================================


def _divide_block(fmt: PositFormat, px, pd, variant: str = DEFAULT_KERNEL_VARIANT):
    """The divider datapath on one uint32 bit-pattern block (n <= 32).

    ``pd`` may be any shape that broadcasts against ``px`` — in particular a
    ``(bm, 1)`` per-row divisor column against a ``(bm, bn)`` dividend block.
    Every divisor-side quantity (decode, alignment, the ``didx`` selection
    index, operand scaling) is then computed ONCE per row on the narrow
    shape; only the recurrence itself runs at full block width.  All datapath
    ops are elementwise, so the broadcast result is bit-identical to running
    the full-width divisor.
    """
    plan = kernel_datapath_plan(fmt, variant)
    assert plan is not None and fmt.n <= 32, (fmt, variant)
    dx = posit_decode(fmt, px)
    dd = posit_decode(fmt, pd)
    frac, t_adj, round_bit, sticky = _divide_fields(
        plan, (dx.sig.astype(_I32),), (dd.sig.astype(_I32),))
    sign = dx.sign ^ dd.sign
    scale = dx.scale - dd.scale + t_adj
    out_nar = dx.is_nar | dd.is_nar | dd.is_zero
    out_zero = dx.is_zero & ~out_nar
    return posit_encode(fmt, sign, scale, frac[0].astype(_U32), round_bit,
                        sticky, out_zero, out_nar)


def _divide_floats_wide(fmt: PositFormat, a, b, variant: str):
    """Fused float32 division for wide formats (n > 32, e.g. posit64).

    Quantization, the W-word recurrence, posit rounding and the float32
    dequantization all happen on in-register word tuples; the pattern
    assembly/rounding reuses the BitVec ``encode_wide``/``decode_wide`` the
    emulate path runs, so both backends are bit-identical by construction.
    """
    from repro.core.bitvec import BitVec, bv_mask
    from repro.core.wide import (
        decode_wide,
        encode_wide,
        float_to_posit_wide,
        posit_wide_to_float,
    )

    plan = kernel_datapath_plan(fmt, variant)
    assert plan is not None and fmt.n > 32, (fmt, variant)
    sx, Tx, sigx, zx, nx = decode_wide(fmt, float_to_posit_wide(fmt, a))
    sd, Td, sigd, zd, nd = decode_wide(fmt, float_to_posit_wide(fmt, b))
    frac, t_adj, round_bit, sticky = _divide_fields(
        plan,
        tuple(l.astype(_I32) for l in sigx.limbs),
        tuple(l.astype(_I32) for l in sigd.limbs))
    sign = sx ^ sd
    scale = Tx - Td + t_adj
    out_nar = nx | nd | zd
    out_zero = zx & ~out_nar
    nlimb = (fmt.F + 31) // 32
    fr = bv_mask(BitVec(tuple(w.astype(_U32) for w in frac[:nlimb]), fmt.F))
    q = encode_wide(fmt, sign, scale, fr, round_bit.astype(_U32), sticky,
                    out_zero, out_nar)
    return posit_wide_to_float(fmt, q)


def divide_floats_block(fmt: PositFormat, a, b,
                        variant: str = DEFAULT_KERNEL_VARIANT):
    """Fused quantize -> SRT divide -> dequantize on one float32 block.

    Works for every planned (fmt, variant), picking the uint32 pattern
    datapath for n <= 32 and the word-tuple wide datapath above it.  This is
    the building block every fused kernel body (elementwise / rowwise /
    softmax / flash-attention normalizer) composes.
    """
    if fmt.n <= 32:
        pa = float_to_posit(fmt, a)
        pb = float_to_posit(fmt, b)
        return posit_to_float(fmt, _divide_block(fmt, pa, pb, variant))
    return _divide_floats_wide(fmt, a, b, variant)


# =====================================================================
# pattern-level Pallas kernel (n <= 32)
# =====================================================================


def _kernel(x_ref, d_ref, o_ref, *, fmt: PositFormat, variant: str):
    o_ref[...] = _divide_block(fmt, x_ref[...], d_ref[...], variant)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6))
def posit_div_pallas(
    fmt: PositFormat,
    px,
    pd,
    block=(64, 256),
    interpret: Optional[bool] = None,
    vmem_limit_bytes: int = VMEM_LIMIT_BYTES,
    variant: str = DEFAULT_KERNEL_VARIANT,
):
    """Tiled Pallas divider over a 2D uint32 array (pre-padded by ops.py)."""
    assert px.ndim == 2 and px.shape == pd.shape
    interpret = resolve_interpret(interpret)
    bm, bn = block
    m, n = px.shape
    assert m % bm == 0 and n % bn == 0, (px.shape, block)
    grid = (m // bm, n // bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, fmt=fmt, variant=variant),
        name="posit_div_pallas",
        out_shape=jax.ShapeDtypeStruct(px.shape, jnp.uint32),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(px.astype(_U32), pd.astype(_U32))
