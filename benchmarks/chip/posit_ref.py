"""Plain posit<n, 2> division in numpy, written from the 2022 Posit Standard.

It shares no code with the program: every positive n-bit pattern is
decoded to float64, and a real value rounds by the standard's bit-string
rule (round to nearest, ties to the even pattern, never to 0 or NaR).  The
rounding boundary between patterns p and p + 1 is the value of the
(n + 1)-bit posit 2p + 1, so rounding is one ``searchsorted``.

Division is exact in float64 before the one rounding: both operands carry
at most n - 4 fraction bits, so a quotient that is not itself a rounding
boundary lies at least 2^-(2n) relative away from one, far above
float64's 2^-53.
"""

from __future__ import annotations

import functools

import numpy as np

ES = 2


def _decode(p: int, n: int) -> float:
    """Value of the positive n-bit posit pattern ``p`` (0 < p < 2^(n-1))."""
    bits = [(p >> i) & 1 for i in range(n - 2, -1, -1)]   # after the sign
    r0 = bits[0]
    m = 1
    while m < len(bits) and bits[m] == r0:
        m += 1
    k = m - 1 if r0 else -m
    rest = bits[m + 1:]                                   # past the stop bit
    e_bits, f_bits = rest[:ES], rest[ES:]
    e = 0
    for b in e_bits:
        e = 2 * e + b
    e <<= ES - len(e_bits)                                # cut bits are 0
    f = 0
    for b in f_bits:
        f = 2 * f + b
    frac = 1.0 + (f / float(1 << len(f_bits)) if f_bits else 0.0)
    return frac * 2.0 ** (k * (1 << ES) + e)


@functools.lru_cache(maxsize=None)
def _tables(n: int):
    top = 1 << (n - 1)
    values = np.array([_decode(p, n) for p in range(1, top)], np.float64)
    bounds = np.array([_decode(2 * p + 1, n + 1) for p in range(1, top - 1)],
                      np.float64)
    return values, bounds


def round_posit(x, n: int = 16) -> np.ndarray:
    """float64 values rounded to posit<n, 2>, returned as float64; NaN and
    infinities become NaN (NaR)."""
    values, bounds = _tables(n)
    x = np.asarray(x, np.float64)
    mag = np.abs(x)
    j = np.searchsorted(bounds, mag, side="left")          # patterns 1 + j
    tie = (j < bounds.size) & (bounds[np.minimum(j, bounds.size - 1)] == mag)
    # pattern 1 + j is even exactly when j is odd; a tie takes the even one
    j = np.where(tie & (j % 2 == 0), j + 1, j)
    out = np.copysign(values[np.minimum(j, values.size - 1)], x)
    out = np.where(mag == 0, 0.0, out)
    return np.where(np.isfinite(x), out, np.nan)


def posit_divide(a, b, n: int = 16) -> np.ndarray:
    """a / b with both operands and the quotient rounded to posit<n, 2>, as
    float32 (every posit16 value is a float32)."""
    qa = round_posit(np.asarray(a, np.float32).astype(np.float64), n)
    qb = round_posit(np.asarray(b, np.float32).astype(np.float64), n)
    with np.errstate(divide="ignore", invalid="ignore"):
        q = qa / qb
    q = np.where(qb == 0, np.nan, q)
    return round_posit(q, n).astype(np.float32)
