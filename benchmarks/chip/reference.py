"""Plain float32 reference of the dense decoder, and its low-precision control.

A copy, kept with the benchmark, of the textbook llama block: RMSNorm,
half-split RoPE, causal GQA softmax attention and a SwiGLU MLP, in
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``,
with no posit numerics, kernels, cache or batching.  The layers run under
``lax.scan`` so that one program serves every depth.  It reads the sizes
from the benchmark's configuration file and the weights the benchmark made
(``weights.py``), and imports nothing of the program.

``precision="fp8"`` is the control: every weight and every activation that
enters a linear layer is rounded to float8 e4m3 with one scale per tensor,
the step below the bfloat16 the configuration computes in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    S, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def logits(params, model: dict, tokens, precision: str = "f32"):
    """(S,) int32 tokens -> (S, vocab) float32 logits."""
    q8 = _fp8 if precision == "fp8" else (lambda a: a)
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


@functools.partial(jax.jit, static_argnames=("model_items", "precision"))
def _readings(params, tokens, targets, *, model_items, precision):
    """Per position: the reference's best logit, its logit of ``targets``,
    and (``precision != "f32"``) its logit of the token the control puts
    first."""
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        ref = logits(params, model, tokens)
    best = jnp.max(ref, axis=-1)
    at = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    if precision == "f32":
        return best, at, at
    with jax.default_matmul_precision("default"):
        low = logits(params, model, tokens, precision)
    first = jnp.argmax(low, axis=-1)
    return best, at, jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]


def widest_gaps(params, model: dict, prompt, served, length: int,
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
                              model_items=tuple(sorted(model.items())),
                              precision=control or "f32")
    sl = slice(n - 1, n - 1 + m)
    best, at, low = (np.asarray(v, np.float64)[sl] for v in (best, at, low))
    return float(np.max(best - at)), float(np.max(best - low))
