"""The plain float32 reference's readings of served tokens, and its
low-precision control.

The reference is the configuration's block (``blocks/<name>.py``
``logits``): the architecture in ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``, with no posit numerics, kernels,
cache or batching, on the weights the benchmark made, importing nothing
of the program.

``precision="fp8"`` is the control: every weight and every activation that
enters a linear layer is rounded to float8 e4m3 with one scale per tensor,
the step below the bfloat16 the configuration computes in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import blocks

F8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit,
                   static_argnames=("block", "model_items", "precision"))
def _readings(params, tokens, targets, *, block, model_items, precision):
    """Per position: the reference's best logit, its logit of ``targets``,
    and (``precision != "f32"``) its logit of the token the control puts
    first."""
    model = dict(model_items)
    logits = blocks.get(block).logits
    with jax.default_matmul_precision("highest"):
        ref = logits(params, model, tokens, lambda a: a)
    best = jnp.max(ref, axis=-1)
    at = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    if precision == "f32":
        return best, at, at
    with jax.default_matmul_precision("default"):
        low = logits(params, model, tokens, _fp8)
    first = jnp.argmax(low, axis=-1)
    return best, at, jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]


def widest_gaps(conf: dict, params, prompt, served, length: int,
                control: str = ""):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every served position of one request, and with
    ``control`` the same for the tokens the control would put first.

    The sequence is padded at the end to ``length`` so that one program
    serves every request of a cell; causal attention keeps the real
    positions' logits as they are."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    n, m = prompt.size, served.size
    toks = np.zeros(length, np.int32)
    toks[:seq.size] = seq
    tgt = np.zeros(length, np.int32)
    tgt[n - 1:n - 1 + m] = served
    best, at, low = _readings(params, jnp.asarray(toks), jnp.asarray(tgt),
                              block=conf["block"],
                              model_items=tuple(sorted(conf["model"].items())),
                              precision=control or "f32")
    sl = slice(n - 1, n - 1 + m)
    best, at, low = (np.asarray(v, np.float64)[sl] for v in (best, at, low))
    return float(np.max(best - at)), float(np.max(best - low))
