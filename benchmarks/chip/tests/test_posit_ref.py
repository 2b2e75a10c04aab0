"""The plain posit16 reference against values worked out by hand from the
2022 Posit Standard (es = 2)."""

import numpy as np

import posit_ref as P


def test_exact_and_saturating_values():
    x = np.array([1.0, 3.0, -0.5, 0.0, 2.0 ** 56, 1e30, 2.0 ** -56, 1e-30,
                  -1e-40, np.nan, np.inf])
    want = np.array([1.0, 3.0, -0.5, 0.0, 2.0 ** 56, 2.0 ** 56, 2.0 ** -56,
                     2.0 ** -56, -(2.0 ** -56), np.nan, np.nan])
    np.testing.assert_array_equal(P.round_posit(x), want)


def test_one_third():
    # 1/3 = 2^-2 * 1.333...: regime 01, exponent 10, 11 fraction bits;
    # 1.333... * 2^11 = 2730.67 rounds to 2731
    assert P.round_posit(1 / 3) == 2731 / 2048 / 4
    assert P.posit_divide(np.float32(1), np.float32(3)) == np.float32(2731 / 2048 / 4)


def test_ties_go_to_the_even_pattern():
    # near 1 the spacing is 2^-11 (regime 10, two exponent bits, eleven
    # fraction bits).  1 + 2^-12 lies halfway between patterns 0x4000 and
    # 0x4001 and goes to 0x4000; 1 + 3 * 2^-12 lies halfway between 0x4001
    # and 0x4002 and goes to 0x4002
    assert P.round_posit(1 + 2.0 ** -12) == 1.0
    assert P.round_posit(1 + 3 * 2.0 ** -12) == 1 + 2.0 ** -10
    assert P.round_posit(1 + 3 * 2.0 ** -13) == 1 + 2.0 ** -11


def test_division_by_zero_is_nar():
    q = P.posit_divide(np.float32([1, 0]), np.float32([0, 2]))
    assert np.isnan(q[0]) and q[1] == 0
