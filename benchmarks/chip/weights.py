"""Random weights for a dense decoder, made from the seed on the device.

One jitted call builds the whole tree in float32, the type the program
serves them in (it casts to bfloat16 inside each step).  The layout is the
program's parameter tree; ``run.py`` checks it against the program's own
shapes before serving.  Scales follow the usual fan-in rule, so that
activations keep their size through the depth; the norm gains are small
random offsets around 1 so that every RMSNorm does real work.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def padded_vocab(model: dict) -> int:
    return -(-model["vocab"] // 256) * 256


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("model_items",))
def _make(key, *, model_items):
    m = dict(model_items)
    L, d, F = m["n_layers"], m["d_model"], m["d_ff"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    V = padded_vocab(m)
    ks = iter(jax.random.split(key, 16))
    gain = 0.1
    embed = {"tok": _normal(next(ks), (V, d), 0.02)}
    if not m["tie_embeddings"]:
        embed["head"] = _normal(next(ks), (d, V), 1 / math.sqrt(d))
    blocks = {
        "ln1": _normal(next(ks), (L, d), gain),
        "attn": {
            "wq": _normal(next(ks), (L, d, H, hd), 1 / math.sqrt(d)),
            "wk": _normal(next(ks), (L, d, KV, hd), 1 / math.sqrt(d)),
            "wv": _normal(next(ks), (L, d, KV, hd), 1 / math.sqrt(d)),
            "wo": _normal(next(ks), (L, H, hd, d), 1 / math.sqrt(H * hd)),
        },
        "ln2": _normal(next(ks), (L, d), gain),
        "mlp": {
            "w1": _normal(next(ks), (L, d, F), 1 / math.sqrt(d)),
            "w3": _normal(next(ks), (L, d, F), 1 / math.sqrt(d)),
            "w2": _normal(next(ks), (L, F, d), 1 / math.sqrt(F)),
        },
    }
    return {"embed": embed, "ln_f": _normal(next(ks), (d,), gain),
            "blocks": blocks}


def make(model: dict, seed: int):
    """The weight tree for ``model`` (the configuration's ``model`` group)
    from ``seed``, any whole number."""
    key = jax.random.PRNGKey(seed % (1 << 63))
    return _make(key, model_items=tuple(sorted(model.items())))
