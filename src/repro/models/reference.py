"""Plain float32 reference forward for the dense decoder family.

Independent of the model code it checks: no posit numerics, no Pallas
kernels, no chunked attention, no cache, no bfloat16.  It is the textbook
llama block (RMSNorm, half-split RoPE, causal GQA softmax attention,
SwiGLU MLP) written out in ``jax.numpy`` over the same parameter tree
:func:`repro.models.transformer.init_params` builds.  Run it under
``jax.default_matmul_precision("highest")`` so that TPU matmuls do not
round their operands to bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, heads, hd), rotating the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def reference_logits(params, cfg: ModelConfig, tokens):
    """(B, S) int32 tokens -> (B, S, vocab) float32 logits."""
    if cfg.family != "dense":
        raise ValueError(f"reference covers the dense family, not {cfg.family!r}")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    B, S = tokens.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = f32(params["embed"]["tok"])[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))
    blocks = params["blocks"]
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a: f32(a[i]), blocks)
        h = _rmsnorm(x, p["ln1"], cfg.norm_eps)
        a = p["attn"]
        q = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wq"]), cfg.rope_theta)
        k = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wk"]), cfg.rope_theta)
        v = jnp.einsum("bsd,dhk->bshk", h, a["wv"])
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"])
        h = _rmsnorm(x, p["ln2"], cfg.norm_eps)
        m = p["mlp"]
        g = jax.nn.silu(h @ m["w1"]) * (h @ m["w3"])
        x = x + g @ m["w2"]
    x = _rmsnorm(x, f32(params["ln_f"]), cfg.norm_eps)
    e = params["embed"]
    head = f32(e["tok"]).T if cfg.tie_embeddings else f32(e["head"])
    return (x @ head)[..., :cfg.vocab]
