"""Posit numerics layer: quantization, posit-division ops, compression."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.posit import PositFormat
from repro.numerics import NumericsConfig, posit_softmax, posit_div_values
from repro.numerics.quant import posit_quantize_ste, posit_round_value
from repro.optim.grad_compress import compress_gradients

CFG = NumericsConfig(posit_division=True, div_format="posit16")
RNG = np.random.default_rng(0)


def test_posit_softmax_close_to_exact():
    x = jnp.asarray(RNG.normal(0, 3, (8, 64)).astype(np.float32))
    ps = posit_softmax(x, CFG)
    es = jax.nn.softmax(x, -1)
    assert float(jnp.max(jnp.abs(ps - es))) < 1e-3
    assert np.allclose(np.asarray(ps.sum(-1)), 1.0, atol=2e-3)


def test_posit_div_values_matches_division():
    a = jnp.asarray(RNG.uniform(0.1, 10, 1000).astype(np.float32))
    b = jnp.asarray(RNG.uniform(0.1, 10, 1000).astype(np.float32))
    d = posit_div_values(a, b, CFG)
    rel = np.abs(np.asarray(d) - np.asarray(a / b)) / np.asarray(a / b)
    assert rel.max() < 2 ** -9  # posit16 has >= 10 significand bits here


def test_posit_div_gradients():
    a = jnp.asarray(RNG.uniform(0.5, 2, 64).astype(np.float32))
    b = jnp.asarray(RNG.uniform(0.5, 2, 64).astype(np.float32))
    ga = jax.grad(lambda a: posit_div_values(a, b, CFG).sum())(a)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(1 / b), rtol=1e-5)


def test_ste_quantize():
    fmt = PositFormat(16)
    x = jnp.asarray(RNG.normal(0, 1, 128).astype(np.float32))
    q = posit_quantize_ste(fmt, x)
    assert float(jnp.max(jnp.abs(q - x) / jnp.abs(x))) < 2 ** -9
    g = jax.grad(lambda x: posit_quantize_ste(fmt, x).sum())(x)
    assert (np.asarray(g) == 1.0).all()


def test_posit_round_idempotent():
    fmt = PositFormat(16)
    x = jnp.asarray(RNG.normal(0, 5, 512).astype(np.float32))
    once = posit_round_value(fmt, x)
    twice = posit_round_value(fmt, once)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))


def test_gradient_compression_error_bound():
    grads = {"a": jnp.asarray(RNG.normal(0, 1e-2, 1000).astype(np.float32)),
             "b": jnp.asarray(RNG.normal(0, 10, (3, 5)).astype(np.float32))}
    comp = compress_gradients(grads, "posit16")
    for k in grads:
        rel = np.abs(np.asarray(comp[k] - grads[k])) / (np.abs(np.asarray(grads[k])) + 1e-12)
        assert rel.max() < 2 ** -8, k


def test_posit_ring_all_reduce_single_axis():
    """shard_map ring all-reduce == psum on a 1-device axis (degenerate)."""
    from repro.optim.grad_compress import posit_ring_all_reduce
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("pod",))
    x = jnp.asarray(RNG.normal(0, 1, 16).astype(np.float32))
    fmt = PositFormat(16)
    out = shard_map(lambda v: posit_ring_all_reduce(v, "pod", fmt),
                    mesh=mesh, in_specs=P(), out_specs=P())(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
