"""Shared neural layers: norms, RoPE, GQA flash attention, MLP, MoE.

All layers are pure functions over parameter pytrees (dicts of jnp arrays).
Compute dtype is bf16 by default with f32 accumulation for reductions; params
stay f32 (the trainer holds the master copy).  Division sites optionally run
through the posit digit-recurrence divider (`cfg.numerics.posit_division`),
either BitVec-emulated or as one fused Pallas kernel
(`cfg.numerics.div_backend`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.numerics.posit_ops import (
    posit_div_values,
    posit_rmsnorm_div,
    posit_softmax,
)
from .config import ModelConfig
from .sharding import constrain

COMPUTE_DTYPE = jnp.bfloat16


def _init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) > 1 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ----------------------------------------------------------------- norms


def rmsnorm(x, w, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if cfg.numerics.posit_division:
        y = posit_rmsnorm_div(xf, jnp.sqrt(ms + cfg.norm_eps), cfg.numerics)
    else:
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# ----------------------------------------------------------------- rope


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., s, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- softmax


def _softmax(x, cfg: ModelConfig, axis=-1):
    if cfg.numerics.posit_division:
        return posit_softmax(x, cfg.numerics, axis=axis)
    return jax.nn.softmax(x, axis=axis)


# ----------------------------------------------------------------- attention


def init_attention(key, cfg: ModelConfig, d_model=None):
    d = d_model or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _init(ks[0], (d, h, hd)),
        "wk": _init(ks[1], (d, kv, hd)),
        "wv": _init(ks[2], (d, kv, hd)),
        "wo": _init(ks[3], (h, hd, d), scale=1.0 / math.sqrt(h * hd)),
    }


def _qkv(params, x, cfg: ModelConfig, positions, rope: bool = True):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.gqa_repeat_kv and cfg.n_kv_heads < cfg.n_heads:
        # §Perf lever: repeat KV to n_heads so attention shards on the head
        # axis — removes the head_dim-contraction all-reduce of the S^2
        # score tensor (the dominant collective in head_dim mode).
        g = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = constrain(k, "batch", "seq", "heads", "head_dim")
        v = constrain(v, "batch", "seq", "heads", "head_dim")
    else:
        k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
        v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    return q, k, v


def flash_attention(q, k, v, cfg: ModelConfig, *, causal: bool,
                    window: int = 0, q_offset: int = 0, kv_start=None,
                    seg_q=None, seg_kv=None, seg_len: int = 0):
    """Chunked online-softmax attention (GQA via head grouping).

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd).  Scans q-chunks in an outer loop
    and kv-chunks in an inner loop with running (max, denom, acc) — the
    standard flash pattern, so no (Sq, Sk) tensor is ever materialized.

    With ``cfg.attn_backend == "fused"`` the whole thing is ONE Pallas
    kernel (`repro.kernels.posit_flash_attn`): the kv-scan accumulates l
    in-register and the final o/l normalizer runs through the in-kernel
    posit SRT datapath; gradients run the fused recompute backward (or the
    float-reference one, per ``cfg.attn_bwd``).  Otherwise, when posit
    division is on, the o/l division below still dispatches shape-aware
    (rowwise fused kernel under div_backend='fused' — no materialized
    broadcast denominator).

    ``kv_start`` is an optional (B,) int32 array of per-sequence pad-prefix
    lengths: key positions < kv_start[b] are masked out.  The serving
    engine's chunked ragged prefill uses it so left-padded short prompts
    never attend pad positions (forward-only path).

    ``seg_q``/``seg_kv`` are optional (B, Sq)/(B, Sk) int32 PER-POSITION
    segment ids for packed multi-prompt prefill (pads carry id -1): score
    entries whose query and key segments differ are masked, so causal
    attention over a concatenation of ``seg_len``-wide prompt segments is
    block-diagonal.  ``seg_len`` (static) is the uniform segment width;
    the chunk/tile sizes are derived from it — NOT from the packed length
    — so chunk boundaries align with segment boundaries and every
    segment's (m, l, acc) accumulation walks bit-identically to running
    that prompt alone at length ``seg_len`` (out-of-segment chunks
    contribute exact zeros; the segment-local chunk split, mask pattern
    and reduction order match the solo call exactly).
    """
    if cfg.attn_backend == "fused":
        from repro.kernels.posit_flash_attn import (
            posit_flash_attention,
            posit_flash_attention_ste,
        )

        nm = cfg.numerics
        if seg_q is not None:
            # packed multi-prompt prefill: forward-only kernel with the
            # block-diagonal segment mask.  The tile size is the SOLO
            # prefill's tile for a seg_len-long prompt (min(128,
            # round_up(seg_len, 8)) == min(128, seg_len) for the power-of-
            # two bucket widths the planner emits), so tiles never
            # straddle segment boundaries and each segment's kv scan is
            # bit-identical to its solo launch.
            blk = min(128, seg_len)
            out = posit_flash_attention(
                nm.div_fmt, q, k, v, causal, window, q_offset, 0.0,
                nm.div_algo, None, blk, blk, kv_start=kv_start, seg_q=seg_q,
                seg_kv=seg_kv)
        elif kv_start is not None:
            # ragged serving prefill: forward-only kernel with the pad-
            # prefix mask (the training path never carries kv_start)
            out = posit_flash_attention(
                nm.div_fmt, q, k, v, causal, window, q_offset, 0.0,
                nm.div_algo, kv_start=kv_start)
        else:
            out = posit_flash_attention_ste(
                nm.div_fmt.n, nm.div_algo, causal, window, q_offset, 0.0,
                q, k, v, cfg.attn_bwd)
        return out.astype(q.dtype)
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV  # query heads per kv head
    scale = 1.0 / math.sqrt(hd)

    def _chunk(S, pref):
        c = min(pref, S)
        while S % c:
            c -= 1
        return c

    has_seg = seg_q is not None
    if has_seg:
        # chunk at the SOLO granularity: _chunk(seg_len) divides seg_len,
        # which divides the packed Sq/Sk, so chunks tile the segments
        bq = _chunk(seg_len, cfg.attn_q_chunk)
        bk = _chunk(seg_len, cfg.attn_kv_chunk)
    else:
        bq = _chunk(Sq, cfg.attn_q_chunk)
        bk = _chunk(Sk, cfg.attn_kv_chunk)
    nq, nk = Sq // bq, Sk // bk

    qr = q.reshape(B, nq, bq, KV, G, hd)
    kr = k.reshape(B, nk, bk, KV, hd)
    vr = v.reshape(B, nk, bk, KV, hd)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, bq)
    k_pos = jnp.arange(Sk).reshape(nk, bk)

    def q_step(_, qi):
        if has_seg:
            qb, qp, sq_b = qi  # (B, bq, KV, G, hd), (bq,), (B, bq)
        else:
            (qb, qp), sq_b = qi, None

        def kv_step(carry, ki):
            m, l, acc = carry
            if has_seg:
                kb, vb, kp, skv_b = ki
            else:
                (kb, vb, kp), skv_b = ki, None
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb)
            if cfg.attn_scores_bf16:
                # keep the (possibly all-reduced) score tensor in bf16; the
                # online-softmax statistics below still accumulate in f32
                s = s.astype(jnp.bfloat16)
            s = s.astype(jnp.float32) * scale
            mask = jnp.ones((bq, kp.shape[0]), dtype=bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window:
                mask &= qp[:, None] - kp[None, :] < window
            s = jnp.where(mask[None, None, None], s, -1e30)
            if kv_start is not None:
                # per-sequence pad prefix: keys before kv_start[b] masked
                pad = kp[None, :] >= kv_start[:, None]        # (B, bk)
                s = jnp.where(pad[:, None, None, None], s, -1e30)
            if has_seg:
                # block-diagonal packed mask: query attends only its own
                # segment's keys (pads carry id -1 in both arrays)
                segm = sq_b[:, :, None] == skv_b[:, None, :]  # (B, bq, bk)
                s = jnp.where(segm[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(qb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, bq), -1e30, dtype=jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), dtype=jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, hd), dtype=jnp.float32)
        kv_xs = (kr.transpose(1, 0, 2, 3, 4), vr.transpose(1, 0, 2, 3, 4),
                 k_pos)
        if has_seg:
            kv_xs += (seg_kv.reshape(B, nk, bk).transpose(1, 0, 2),)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), kv_xs)
        if cfg.numerics.posit_division:
            out = posit_div_values(acc, l[..., None] + 1e-30, cfg.numerics)
        else:
            out = acc / (l[..., None] + 1e-30)
        return None, out.astype(qb.dtype)  # (B, KV, G, bq, hd)

    q_xs = (qr.transpose(1, 0, 2, 3, 4, 5), q_pos)
    if has_seg:
        q_xs += (seg_q.reshape(B, nq, bq).transpose(1, 0, 2),)
    _, outs = jax.lax.scan(q_step, None, q_xs)
    # outs: (nq, B, KV, G, bq, hd) -> (B, Sq, H, hd)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hd)
    return out


# ------------------------------------------- TP-invariant contractions
#
# The sharded serve engine runs these layers inside ``shard_map`` over
# ``cfg.tp_axis``.  Most of the datapath is TRIVIALLY bit-identical per
# shard (projections are output-sharded: each shard computes a head/ffn
# SLICE of the very same einsum, and softmax is per-head) — but the two
# contractions that REDUCE over a sharded dimension (attention output
# over heads, MLP down-projection over d_ff) are not associativity-safe:
# a per-shard partial sum + psum would combine in a different order than
# the single-device einsum and change low bits.  ``cfg.tp_groups`` fixes
# this by splitting those reductions into a static number of groups
# combined in a FIXED ascending order at every TP degree (the reference
# engine computes the same grouped form at TP=1), which is what the
# sharded-serving bit-identity gate rides on.


def _tp_local_groups(cfg: ModelConfig) -> int:
    return cfg.tp_groups // (cfg.tp_size if cfg.tp_axis is not None else 1)


def tp_group_combine(partials, cfg: ModelConfig):
    """Fixed-order combine of per-group partial sums (leading group axis).

    Under ``cfg.tp_axis`` each shard holds ``tp_groups / tp_size`` group
    partials; they are all-gathered (an EXACT concatenation — no
    arithmetic) so every device sums ALL ``tp_groups`` partials locally
    in ascending group order.  The summation tree is therefore identical
    at every TP degree, making the result bit-identical across degrees.
    A plain ``psum`` of per-shard sums would NOT have this property:
    f32/bf16 addition is not associative.
    """
    if cfg.tp_axis is not None:
        partials = jax.lax.all_gather(partials, cfg.tp_axis, axis=0,
                                      tiled=True)
    out = partials[0]
    for g in range(1, partials.shape[0]):
        out = out + partials[g]
    return out


def wo_project(o, wo, cfg: ModelConfig):
    """Attention output projection ``einsum("bshk,hkd->bsd", o, wo)``.

    With ``cfg.tp_groups`` set, the head contraction is split into fixed
    head groups combined in ascending order (:func:`tp_group_combine`);
    under ``cfg.tp_axis`` each shard contracts its local head slice —
    that axis' share of the same global groups — so the sharded result
    is bit-identical to the reference grouped one.  ``tp_groups == 0``
    keeps the historical single-einsum numerics.
    """
    wo = wo.astype(o.dtype)
    if not cfg.tp_groups:
        return jnp.einsum("bshk,hkd->bsd", o, wo)
    gl = _tp_local_groups(cfg)
    B, S, H, hd = o.shape
    og = o.reshape(B, S, gl, H // gl, hd)
    wg = wo.reshape(gl, H // gl, hd, wo.shape[-1])
    parts = jnp.einsum("bsghk,ghkd->gbsd", og, wg)
    return tp_group_combine(parts, cfg)


def attention_block(params, x, cfg: ModelConfig, positions, *, causal=True,
                    window=0, rope=True):
    q, k, v = _qkv(params, x, cfg, positions, rope=rope)
    o = flash_attention(q, k, v, cfg, causal=causal, window=window)
    o = constrain(o, "batch", "seq", "heads", "head_dim")
    return wo_project(o.astype(x.dtype), params["wo"], cfg)


def cross_attention_block(params, x, mem_kv, cfg: ModelConfig):
    """Decoder cross-attention; mem_kv = (k, v) precomputed from the encoder."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k, v = mem_kv
    o = flash_attention(q, k.astype(dt), v.astype(dt), cfg, causal=False)
    return wo_project(o.astype(dt), params["wo"], cfg)


def _decode_project(params, x, pos, start, cfg: ModelConfig, rope: bool):
    """Shared decode-step front end: q/k/v projection, RoPE at the per-slot
    RELATIVE position (``pos - start``), optional posit KV quantization.

    Factored out of :func:`decode_attention` so the paged-cache decode path
    produces bit-identical k/v entries from the same code.
    """
    dt = x.dtype
    positions = pos[:, None]
    if start is not None:
        positions = positions - start[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.numerics.kv_cache_format:
        # posit-quantized KV storage: entries are rounded to the posit grid
        # at insertion (wire format uint16/uint8; values emulated here)
        from repro.numerics.formats import resolve_format
        from repro.numerics.quant import posit_round_value

        pf = resolve_format(cfg.numerics.kv_cache_format)
        k = posit_round_value(pf, k.astype(jnp.float32)).astype(k.dtype)
        v = posit_round_value(pf, v.astype(jnp.float32)).astype(v.dtype)
    return q, k, v


def _decode_attend_fused(q, ck, cv, pos, start, cfg: ModelConfig,
                         block_tables=None):
    """One Pallas launch for all slots at heterogeneous positions: the
    causal mask uses per-sequence q_pos, the per-slot cache length is
    kv_len = pos + 1, and start masks any left-pad prefix.  With
    ``block_tables`` the k/v operands are global block pools and the kernel
    gathers pages in-kernel (same tile geometry, bit-identical scan)."""
    from repro.kernels.posit_flash_attn import posit_flash_attention

    nm = cfg.numerics
    return posit_flash_attention(
        nm.div_fmt, q.astype(jnp.float32), ck.astype(jnp.float32),
        cv.astype(jnp.float32), True, 0, 0, 0.0, nm.div_algo,
        kv_start=start, kv_len=pos + 1, q_pos=pos,
        block_tables=block_tables)


def _decode_attend_xla(q, ck, cv, pos, start, window: int, cfg: ModelConfig):
    """XLA decode attention over a dense (B, S, KV, hd) cache view: masked
    scores over rows [start[b], pos[b]] and a posit-divided softmax."""
    dt = q.dtype
    B, S, KV, hd = ck.shape
    # head counts from the OPERANDS, not cfg: under shard_map both q and the
    # cache carry the per-shard head slice, and cfg.n_heads is global
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg[:, 0], ck.astype(dt))
    s = s.astype(jnp.float32) / math.sqrt(hd)
    kpos = jnp.arange(S)
    mask = kpos[None, None, None, :] <= pos[:, None, None, None]
    if window:
        mask &= kpos[None, None, None, :] > pos[:, None, None, None] - window
    if start is not None:
        mask = mask & (kpos[None, None, None, :]
                       >= start[:, None, None, None])
    s = jnp.where(mask, s, -1e30)
    p = _softmax(s, cfg, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(dt), cv.astype(dt))
    return o.reshape(B, 1, H, hd)


def decode_attention(params, x, cache_k, cache_v, pos, cfg: ModelConfig,
                     *, window: int = 0, rope: bool = True, start=None):
    """Single-token attention against a (B, S, KV, hd) cache; returns output
    and the updated cache entries (caller writes them).

    ``pos`` is a PER-SLOT (B,) int32 vector of decode positions (a scalar
    is broadcast): slot b's K/V are written at cache row pos[b], its RoPE
    phase is pos[b] (relative to start[b]), and its attention mask covers
    rows [start[b], pos[b]] — so every batch slot can sit at a different
    sequence offset inside one jitted step (continuous batching).

    ``start`` is an optional (B,) int32 array of per-sequence start offsets
    (left-padded ragged prompts): cache positions < start[b] are masked
    out and RoPE positions are taken RELATIVE to start[b], so a short
    prompt decodes identically alone, batched, or admitted mid-flight.

    Under ``cfg.attn_backend == "fused"`` the attention itself runs through
    the posit flash Pallas kernel with per-sequence ``q_pos``/``kv_len``/
    ``kv_start`` inputs — per-slot decode positions end to end.
    """
    dt = x.dtype
    B, S, KV, hd = cache_k.shape
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)
    q, k, v = _decode_project(params, x, pos, start, cfg, rope)
    # per-slot cache write: slot b's row pos[b] (clamped in-bounds; parked
    # slots sit at row 0 and overwrite it, which admission re-prefills)
    bidx = jnp.arange(B)
    pos_c = jnp.minimum(pos, S - 1)
    ck = cache_k.at[bidx, pos_c].set(k[:, 0].astype(cache_k.dtype))
    cv = cache_v.at[bidx, pos_c].set(v[:, 0].astype(cache_v.dtype))

    if cfg.attn_backend == "fused" and not window:
        o = _decode_attend_fused(q, ck, cv, pos, start, cfg)
    else:
        o = _decode_attend_xla(q, ck, cv, pos, start, window, cfg)
    out = wo_project(o.astype(dt), params["wo"], cfg)
    return out, ck, cv


def decode_attention_paged(params, x, pool_k, pool_v, block_tables, pos,
                           cfg: ModelConfig, *, start=None):
    """Single-token attention against a PAGED cache; returns output and the
    updated block pools (caller writes them).

    ``pool_k``/``pool_v`` are global block pools ``(num_blocks, block_size,
    KV, hd)`` shared by every slot; ``block_tables`` is the per-slot
    ``(B, max_blocks)`` int32 map from logical cache row ``r`` of slot
    ``b`` to pool row ``(block_tables[b, r // bs], r % bs)``.  Slot b's new
    K/V land in its ``pos[b]``-th logical row's page — a 2-element scatter
    into the pool instead of the dense path's per-slot row write.  Parked
    slots (all-zero table rows) write block 0, the reserved sink page no
    live table ever maps.

    The attention itself is layout-invariant: the fused backend hands the
    pools plus table straight to the Pallas kernel (in-kernel page gather,
    same tile geometry as dense — see ``kernels/posit_flash_attn``); the
    XLA backend gathers the table into the dense ``(B, S, KV, hd)`` view —
    row-for-row identical contents — and runs the same masked softmax.
    Either way the output is bit-identical to :func:`decode_attention` on
    the equivalent dense cache.
    """
    dt = x.dtype
    NB, bs, KV, hd = pool_k.shape
    B, mb = block_tables.shape
    S = mb * bs  # virtual per-slot sequence length (= dense max_seq)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)
    q, k, v = _decode_project(params, x, pos, start, cfg, rope=True)
    pos_c = jnp.minimum(pos, S - 1)
    bid = jnp.take_along_axis(block_tables, (pos_c // bs)[:, None],
                              axis=1)[:, 0]
    row = pos_c % bs
    pk = pool_k.at[bid, row].set(k[:, 0].astype(pool_k.dtype))
    pv = pool_v.at[bid, row].set(v[:, 0].astype(pool_v.dtype))

    if cfg.attn_backend == "fused":
        o = _decode_attend_fused(q, pk, pv, pos, start, cfg,
                                 block_tables=block_tables)
    else:
        ck = pk[block_tables].reshape(B, S, KV, hd)
        cv = pv[block_tables].reshape(B, S, KV, hd)
        o = _decode_attend_xla(q, ck, cv, pos, start, 0, cfg)
    out = wo_project(o.astype(dt), params["wo"], cfg)
    return out, pk, pv


def prefill_attention(params, x, cache_k, cache_v, cfg: ModelConfig,
                      positions, start=None, seg_q=None, seg_kv=None,
                      seg_len=0):
    """Whole-prompt attention that fills cache slots [0, S) in ONE shot.

    The chunked-prefill counterpart of :func:`decode_attention`: all S
    prompt tokens are projected, roped (``positions`` already carries the
    per-sequence relative offsets), optionally posit-quantized for KV
    storage, written into the decode cache with a single
    ``dynamic_update_slice``, and attended causally via
    :func:`flash_attention` — which routes through the fused Pallas kernel
    under ``cfg.attn_backend == "fused"``, so serving prefill exercises the
    same kernel the trainer does.  ``start`` masks per-sequence pad
    prefixes (left-padded ragged batches).

    ``seg_q``/``seg_kv``/``seg_len`` switch on PACKED multi-prompt
    prefill: (B, S) int32 per-position segment ids (query pads -2, key
    pads -1) make the single concatenated sequence attend
    block-diagonally — N prompts prefill in one launch, each
    bit-identical to its solo prefill of width ``seg_len``.
    """
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.numerics.kv_cache_format:
        from repro.numerics.formats import resolve_format
        from repro.numerics.quant import posit_round_value

        pf = resolve_format(cfg.numerics.kv_cache_format)
        k = posit_round_value(pf, k.astype(jnp.float32)).astype(k.dtype)
        v = posit_round_value(pf, v.astype(jnp.float32)).astype(v.dtype)
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                      (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                      (0, 0, 0, 0))
    o = flash_attention(q, k, v, cfg, causal=True, kv_start=start,
                        seg_q=seg_q, seg_kv=seg_kv, seg_len=seg_len)
    out = wo_project(o.astype(dt), params["wo"], cfg)
    return out, ck, cv


def prefill_suffix_attention(params, x, cache_k, cache_v, cfg: ModelConfig,
                             positions, start, t0: int):
    """Prefix-sharing prefill: attend the SUFFIX tokens ``[t0, t0+S)``
    against a cache whose rows ``[0, t0)`` already hold a shared prefix.

    The suffix projections are written at cache offset ``t0`` and the
    attention keys are ``concat(cache[:t0], fresh_suffix)`` with query
    offset ``t0`` — so the kv sequence the flash scan walks has the exact
    length, order and contents a full-prompt :func:`prefill_attention`
    would have built (the cached prefix rows are a pure function of the
    prefix tokens when prefill runs unpadded at start 0, and the cache
    dtype is the compute dtype).  The kv tile size depends only on the kv
    length, which is identical, so the online-softmax accumulation — hence
    the suffix logits — are bit-identical to the unshared prefill.  With
    ``t0 == 0`` this IS :func:`prefill_attention` (empty prefix concat).

    Not valid under ``numerics.kv_cache_format``: prefill attends
    unquantized fresh k/v but the cache stores quantized rows, so a reused
    prefix would change the numerics — the engine disables sharing there.
    """
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.numerics.kv_cache_format:
        from repro.numerics.formats import resolve_format
        from repro.numerics.quant import posit_round_value

        pf = resolve_format(cfg.numerics.kv_cache_format)
        k = posit_round_value(pf, k.astype(jnp.float32)).astype(k.dtype)
        v = posit_round_value(pf, v.astype(jnp.float32)).astype(v.dtype)
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                      (0, t0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                      (0, t0, 0, 0))
    if t0:
        k_all = jnp.concatenate([cache_k[:, :t0].astype(dt), k], axis=1)
        v_all = jnp.concatenate([cache_v[:, :t0].astype(dt), v], axis=1)
    else:
        k_all, v_all = k, v
    o = flash_attention(q, k_all, v_all, cfg, causal=True, q_offset=t0,
                        kv_start=start)
    out = wo_project(o.astype(dt), params["wo"], cfg)
    return out, ck, cv


# ----------------------------------------------------------------- MLP


def init_mlp(key, cfg: ModelConfig, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w1": _init(ks[0], (d, ff)),
        "w3": _init(ks[1], (d, ff)),
        "w2": _init(ks[2], (ff, d)),
    }


def mlp_block(params, x, cfg: ModelConfig):
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, params["w1"].astype(dt))
    g = jnp.einsum("bsd,df->bsf", x, params["w3"].astype(dt))
    h = jax.nn.silu(h) * g
    h = constrain(h, "batch", "seq", "ffn")
    w2 = params["w2"].astype(dt)
    if not cfg.tp_groups:
        return jnp.einsum("bsf,fd->bsd", h, w2)
    # grouped fixed-order down-projection: the d_ff reduction is split into
    # tp_groups slices combined in ascending order (TP-degree-invariant
    # bits — see tp_group_combine)
    gl = _tp_local_groups(cfg)
    B, S, F = h.shape
    parts = jnp.einsum("bsgf,gfd->gbsd", h.reshape(B, S, gl, F // gl),
                       w2.reshape(gl, F // gl, w2.shape[-1]))
    return tp_group_combine(parts, cfg)


# ----------------------------------------------------------------- MoE


def init_moe(key, cfg: ModelConfig):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _init(ks[0], (d, E), scale=0.02),
        "w1": _init(ks[1], (E, d, ff)),
        "w3": _init(ks[2], (E, d, ff)),
        "w2": _init(ks[3], (E, ff, d)),
    }


def moe_block(params, x, cfg: ModelConfig):
    """Top-k MoE, capacity-bounded scatter/gather dispatch *per batch row*.

    The dispatch buffer keeps a leading batch dim sharded over DP, so expert
    compute is C_row-bounded per data shard (no DP-replicated global
    capacity); experts shard over the model axis (EP) and GSPMD emits the
    dispatch/combine all-to-alls.  FLOPs ~= active-expert FLOPs *
    capacity_factor.  Rank computation uses associative_scan (XLA cost models
    long cumsums quadratically).
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    dt = x.dtype
    C = max(int(math.ceil(S * k / E * cfg.capacity_factor)), 1)

    logits = jnp.einsum("bsd,de->bse", x, params["router"].astype(dt)).astype(jnp.float32)
    probs = _softmax(logits, cfg, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)          # (B, S, k)
    if cfg.numerics.posit_division:
        from repro.numerics.posit_ops import posit_router_norm
        gate = posit_router_norm(gate, cfg.numerics)
    else:
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)

    # rank of each (token, choice) within its expert, per batch row
    onehot = jax.nn.one_hot(eid, E, dtype=jnp.int32)          # (B, S, k, E)
    flat_oh = onehot.reshape(B, S * k, E)
    csum = jax.lax.associative_scan(jnp.add, flat_oh, axis=1)
    ranks = (csum - flat_oh).reshape(B, S, k, E)
    rank = (ranks * onehot).sum(-1)                           # (B, S, k)
    keep = rank < C
    dest = jnp.where(keep, eid * C + rank, E * C)             # (B, S, k)

    # dispatch: scatter tokens into (B, E*C+1, D)
    binx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S * k))
    vals = jnp.broadcast_to(x[:, :, None, :], (B, S, k, D)).reshape(B, S * k, D)
    buf = jnp.zeros((B, E * C + 1, D), dtype=dt)
    buf = buf.at[binx, dest.reshape(B, S * k)].add(vals)
    xe = buf[:, : E * C].reshape(B, E, C, D)
    xe = constrain(xe, "batch", "experts", None, None)

    h = jnp.einsum("becd,edf->becf", xe, params["w1"].astype(dt))
    g = jnp.einsum("becd,edf->becf", xe, params["w3"].astype(dt))
    h = jax.nn.silu(h) * g
    h = constrain(h, "batch", "experts", None, None)
    ye = jnp.einsum("becf,efd->becd", h, params["w2"].astype(dt))
    ye = constrain(ye, "batch", "experts", None, None)

    # combine: gather back and weight
    yflat = jnp.concatenate(
        [ye.reshape(B, E * C, D), jnp.zeros((B, 1, D), dtype=dt)], axis=1)
    ytok = yflat[binx, dest.reshape(B, S * k)].reshape(B, S, k, D)
    y = (ytok * gate[..., None].astype(dt) * keep[..., None]).sum(2)
    return y


# ----------------------------------------------------------------- embedding


def init_embed(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    p = {"tok": _init(ks[0], (cfg.padded_vocab, cfg.d_model), scale=0.02)}
    if not cfg.tie_embeddings:
        p["head"] = _init(ks[1], (cfg.d_model, cfg.padded_vocab))
    return p


def embed(params, tokens, cfg: ModelConfig):
    # NOTE: no with_sharding_constraint here — re-sharding a gather output
    # from a model-sharded table inside a scan body trips an XLA SPMD
    # partitioner verifier bug (see DESIGN.md); GSPMD propagation handles it.
    tok = params["tok"].astype(COMPUTE_DTYPE)
    if cfg.tp_axis is None:
        return tok[tokens]
    # vocab-sharded table under shard_map: every shard gathers its LOCAL
    # rows, the per-shard gathers are all-gathered, and each token SELECTS
    # its owner shard's row — pure data movement, no arithmetic, so the
    # embedded activations are bit-identical to the unsharded gather.
    vl = tok.shape[0]
    owner = tokens // vl                       # shard that owns each token
    rows = jax.lax.all_gather(tok[tokens % vl], cfg.tp_axis, axis=0,
                              tiled=False)
    x = rows[0]
    for t in range(1, cfg.tp_size):
        x = jnp.where((owner == t)[..., None], rows[t], x)
    return x


def logits(params, x, cfg: ModelConfig):
    w = params["tok"] if cfg.tie_embeddings else params["head"]
    w = w.T if cfg.tie_embeddings else w
    out = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    if cfg.tp_axis is not None:
        # vocab-sharded head: each shard computes its logit slice and the
        # concat (all-gather over the vocab axis) is exact, so the full
        # logit vector is bit-identical to the unsharded einsum
        out = jax.lax.all_gather(out, cfg.tp_axis, axis=out.ndim - 1,
                                 tiled=True)
    return constrain(out, "batch", "seq", "vocab")
