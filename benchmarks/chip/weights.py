"""Random weights from the seed, and the check that they fit the program.

A configuration's block (``blocks/<name>.py``) makes its tree on the
device in one jitted call, from ``key(seed)`` with ``normal`` draws;
``check`` holds the tree against the program's own parameter layout
before serving.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def key(seed: int):
    """The PRNG key of ``seed``, any whole number."""
    return jax.random.PRNGKey(seed % (1 << 63))


def normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def padded_vocab(model: dict) -> int:
    """Rows of the program's embedding: the vocabulary padded to 256."""
    return -(-model["vocab"] // 256) * 256


def check(conf: dict, params, cfg) -> None:
    """Exit unless ``params`` has the shapes and dtypes of the program's
    parameter tree for the model config ``cfg``."""
    from repro.models import transformer as T

    want = jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    have = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != have:
        raise SystemExit(f"the weight tree of block {conf['block']!r} does "
                         "not match the program's parameter layout")
