"""Posit16 gradient compression for cross-pod all-reduce (beyond-paper).

The pod<->pod ICI/DCN links are the slowest hop in a multi-pod mesh.  We cut
the bytes on that hop in half by shipping gradients as 16-bit posit patterns
(the paper's number system as a *wire format*) in a ring all-reduce over the
``pod`` axis implemented with ``lax.ppermute`` under ``shard_map``:

    within-pod:  psum over ('data', ...) in f32 as usual
    across pods: ring reduce-scatter + all-gather with posit16 payloads,
                 decode -> accumulate in f32 -> re-encode each hop.

Lossy (posit16 quantization error per hop, bounded by ~2^-12 relative), off
by default, selected by ``NumericsConfig.grad_compress_format``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.posit import PositFormat, float_to_posit, posit_to_float
from repro.numerics.formats import resolve_format


def _enc(fmt, x):
    p = float_to_posit(fmt, x)
    return p.astype(jnp.uint16 if fmt.n == 16 else jnp.uint32)


def _dec(fmt, w):
    return posit_to_float(fmt, w.astype(jnp.uint32))


def posit_ring_all_reduce(x, axis_name: str, fmt: PositFormat):
    """Ring all-reduce along ``axis_name`` with posit-compressed payloads.

    Must run inside shard_map with ``axis_name`` unreduced.  x: f32 array.
    """
    # psum of a python scalar folds to the (static) axis size at trace time;
    # jax.lax.axis_size does not exist in the pinned JAX version.
    n = jax.lax.psum(1, axis_name)
    if n == 1:
        return x
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = x
    buf = _enc(fmt, x)
    for _ in range(n - 1):
        buf = jax.lax.ppermute(buf, axis_name, perm)
        acc = acc + _dec(fmt, buf)
        buf = _enc(fmt, _dec(fmt, buf))  # re-encode what we forward
    return acc


def compress_gradients(grads, fmt_name: str):
    """Quantize a gradient pytree to posit values (fake-quant, f32 storage)."""
    fmt = resolve_format(fmt_name)

    def q(g):
        return posit_to_float(fmt, float_to_posit(fmt, g.astype(jnp.float32)))

    return jax.tree.map(q, grads)


def make_compressed_psum(mesh, fmt_name: str, pod_axis: str = "pod"):
    """Returns grads -> all-reduced grads with posit16 pod-axis traffic.

    Usage: called on the *already data-axis-reduced* gradient pytree inside
    the train step when a multi-pod mesh is active.
    """
    fmt = resolve_format(fmt_name)

    def ar(g):
        def inner(gs):
            return posit_ring_all_reduce(gs, pod_axis, fmt)

        spec = P()  # replicated within pod; ring over pods
        return shard_map(inner, mesh=mesh, in_specs=spec, out_specs=spec)(g)

    return lambda grads: jax.tree.map(ar, grads)
