"""Posit formats as wire/storage compression (beyond-paper application).

Quantifies: (1) posit16/8 gradient-compression error vs bf16/f16 on realistic
gradient distributions, (2) the posit16 ring all-reduce reproducing psum
within quantization error, (3) checkpoint size reduction.

    PYTHONPATH=src python examples/posit_compression.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.posit import PositFormat, float_to_posit, posit_to_float
from repro.optim.grad_compress import posit_ring_all_reduce
from jax.sharding import PartitionSpec as P


def relerr(a, b):
    return float(np.max(np.abs(a - b) / (np.abs(b) + 1e-12)))


def main():
    rng = np.random.default_rng(0)
    # gradients are heavy-tailed around 0 — posit's tapered precision shines
    g = (rng.standard_t(4, 200000) * 1e-3).astype(np.float32)

    print("format    bits  max-rel-err   rms-err")
    for name, f in (
        ("posit16", lambda x: posit_to_float(PositFormat(16), float_to_posit(PositFormat(16), x))),
        ("posit8", lambda x: posit_to_float(PositFormat(8), float_to_posit(PositFormat(8), x))),
        ("bf16", lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)),
        ("f16", lambda x: x.astype(jnp.float16).astype(jnp.float32)),
    ):
        got = np.asarray(f(jnp.asarray(g)))
        bits = 8 if name == "posit8" else 16
        rms = float(np.sqrt(np.mean((got - g) ** 2)))
        print(f"{name:8s} {bits:4d}  {relerr(got, g):10.2e}  {rms:9.2e}")

    # ring all-reduce with posit16 payloads on a virtual 1-axis mesh
    mesh = jax.make_mesh((1,), ("pod",))
    x = jnp.asarray(rng.normal(0, 1, 1024).astype(np.float32))
    from jax import shard_map

    out = shard_map(
        lambda v: posit_ring_all_reduce(v, "pod", PositFormat(16)),
        mesh=mesh, in_specs=P(), out_specs=P())(x)
    print("\nring all-reduce (1 pod, degenerate) exact:",
          bool((np.asarray(out) == np.asarray(x)).all()))
    print("on a 2-pod mesh the wire payload is uint16 posit patterns: "
          "2x fewer bytes on the pod-interconnect hop (see EXPERIMENTS.md §Perf)")

    # checkpoint compression
    params = {"w": jnp.asarray(rng.normal(0, 0.02, (1024, 1024)).astype(np.float32))}
    p16 = float_to_posit(PositFormat(16), params["w"]).astype(jnp.uint16)
    err = relerr(np.asarray(posit_to_float(PositFormat(16), p16.astype(jnp.uint32))),
                 np.asarray(params["w"]))
    print(f"\ncheckpoint: f32 {params['w'].nbytes/2**20:.1f} MiB -> "
          f"posit16 {p16.nbytes/2**20:.1f} MiB (max rel err {err:.1e})")


if __name__ == "__main__":
    main()
