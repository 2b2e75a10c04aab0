"""The dense llama block: RMSNorm, GQA softmax attention with half-split
RoPE, and a SwiGLU MLP, in every layer alike.

Its weights, its plain float32 reference, the work its per-layer metrics
count, and the sites where the program divides (``blocks/__init__.py``
says what each is for).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import weights as W
from blocks import Site

# ------------------------------------------------------------------ weights


@functools.partial(jax.jit, static_argnames=("model_items",))
def _make(key, *, model_items):
    """The whole tree in float32, the type the program serves it in (it
    casts to bfloat16 inside each step).  Scales follow the fan-in rule,
    so that activations keep their size through the depth; the norm gains
    are small random offsets around 1 so that every RMSNorm does real
    work."""
    m = dict(model_items)
    L, d, F = m["n_layers"], m["d_model"], m["d_ff"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    V = W.padded_vocab(m)
    normal = W.normal
    ks = iter(jax.random.split(key, 16))
    gain = 0.1
    embed = {"tok": normal(next(ks), (V, d), 0.02)}
    if not m["tie_embeddings"]:
        embed["head"] = normal(next(ks), (d, V), 1 / math.sqrt(d))
    blocks = {
        "ln1": normal(next(ks), (L, d), gain),
        "attn": {
            "wq": normal(next(ks), (L, d, H, hd), 1 / math.sqrt(d)),
            "wk": normal(next(ks), (L, d, KV, hd), 1 / math.sqrt(d)),
            "wv": normal(next(ks), (L, d, KV, hd), 1 / math.sqrt(d)),
            "wo": normal(next(ks), (L, H, hd, d), 1 / math.sqrt(H * hd)),
        },
        "ln2": normal(next(ks), (L, d), gain),
        "mlp": {
            "w1": normal(next(ks), (L, d, F), 1 / math.sqrt(d)),
            "w3": normal(next(ks), (L, d, F), 1 / math.sqrt(d)),
            "w2": normal(next(ks), (L, F, d), 1 / math.sqrt(F)),
        },
    }
    return {"embed": embed, "ln_f": normal(next(ks), (d,), gain),
            "blocks": blocks}


def make_weights(model: dict, seed: int):
    return _make(W.key(seed), model_items=tuple(sorted(model.items())))


# ------------------------------------------------------------------ reference


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    S, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def logits(params, model: dict, tokens, q8):
    """(S,) int32 tokens -> (S, vocab) float32 logits; the layers run
    under ``lax.scan`` so that one program serves every depth."""
    H, KV = model["n_heads"], model["n_kv_heads"]
    hd, eps, theta = model["head_dim"], model["norm_eps"], model["rope_theta"]
    S = tokens.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    x = params["embed"]["tok"][tokens]

    def layer(x, p):
        h = q8(_rmsnorm(x, p["ln1"], eps))
        a = p["attn"]
        q = _rope(jnp.einsum("sd,dhk->shk", h, q8(a["wq"])), theta)
        k = _rope(jnp.einsum("sd,dhk->shk", h, q8(a["wk"])), theta)
        v = jnp.einsum("sd,dhk->shk", h, q8(a["wv"]))
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("shk,hkd->sd", q8(o), q8(a["wo"]))
        h = q8(_rmsnorm(x, p["ln2"], eps))
        m = p["mlp"]
        g = jax.nn.silu(h @ q8(m["w1"])) * (h @ q8(m["w3"]))
        return x + q8(g) @ q8(m["w2"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = q8(_rmsnorm(x, params["ln_f"], eps))
    e = params["embed"]
    head = e["tok"].T if model["tie_embeddings"] else e["head"]
    return (x @ q8(head))[:, :model["vocab"]]


# ------------------------------------------------------------------ work
#
# What the model and the attention need, whatever implements them: real
# prompt tokens (not bucket padding), active slots (not parked ones), live
# KV rows (not ``max_seq``), and the model's ``head_dim`` in the cache's
# dtype (not a padded or widened kernel layout), so that a faster
# implementation of the same work reads as a higher share.  A FLOP is one
# multiply or one add; a matrix product of (m, k) by (k, n) is 2 m k n.


def linear_params(model: dict) -> int:
    """Weights that one token multiplies through in the decoder layers."""
    d, F = model["d_model"], model["d_ff"]
    H, KV, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return model["n_layers"] * per_layer


def attention_flops(model: dict, queries: int, keys: int) -> int:
    """Scores and weighted values of ``queries`` rows over ``keys`` rows
    each, in every layer and head."""
    return 4 * model["n_layers"] * model["n_heads"] * model["head_dim"] \
        * queries * keys


def decode_flops(model: dict, live_rows) -> int:
    """One decode step: one token per active slot, slot ``i`` attending
    ``live_rows[i]`` cache rows (its prompt and the tokens so far,
    including the row this step writes), and its logits."""
    lin = 2 * linear_params(model) + 2 * model["d_model"] * model["vocab"]
    return sum(lin + attention_flops(model, 1, int(n)) for n in live_rows)


def prefill_flops(model: dict, prompt_len: int) -> int:
    """One solo prefill of ``prompt_len`` real tokens: every token through
    the layers, causal attention over rows 1..n, and the last position's
    logits, which give the first token."""
    n = int(prompt_len)
    causal_pairs = n * (n + 1) // 2
    return (2 * linear_params(model) * n
            + attention_flops(model, 1, causal_pairs)
            + 2 * model["d_model"] * model["vocab"])


def flash_decode_bytes(model: dict, live_rows, kv_bytes: int = 2,
                       act_bytes: int = 2) -> int:
    """HBM bytes decode attention needs in one step, over every layer: each
    active slot's live K and V rows at ``head_dim``, read once per KV head
    in the cache's dtype (``kv_bytes``), plus its query and output rows."""
    H, KV, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    per_layer = sum(2 * int(n) * KV * hd * kv_bytes + 2 * H * hd * act_bytes
                    for n in live_rows)
    return model["n_layers"] * per_layer


def rmsnorm_divide_bytes(model: dict, prompt_len: int) -> int:
    """HBM bytes of the RMSNorm divides in one prefill of ``prompt_len``
    real tokens: two norms per layer and the final one, each reading a
    float32 numerator row and one float32 divisor per row and writing a
    float32 quotient row."""
    rows = (2 * model["n_layers"] + 1) * int(prompt_len)
    return rows * (2 * 4 * model["d_model"] + 4)


# ------------------------------------------------------------------ division sites
#
# Every RMSNorm divides x / rms on the rowwise SRT kernel: its quotients
# are held bit for bit.  Every attention's ``acc / l`` normalizer divides
# inside the flash kernel, whose accumulators never leave it: what can be
# held is that each float32 output is a posit value.

DIVISION_SITES = (
    Site("repro.models.layers", "posit_rmsnorm_div", "quotient",
         lambda x, rms, cfg: (x, rms)),
    Site("repro.kernels.posit_flash_attn", "posit_flash_attention", "grid"),
)
