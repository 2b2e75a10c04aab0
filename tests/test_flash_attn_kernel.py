"""Posit flash-attention kernel: accuracy, GQA, masking, grads, routing,
and the fused recompute backward (residuals, gradient equivalence, no
(Sq, Sk) intermediate)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.posit import PositFormat
from repro.kernels.posit_flash_attn import (
    posit_flash_attention,
    posit_flash_attention_fwd,
    posit_flash_attention_ste,
)
from repro.models import layers as L
from repro.numerics import NumericsConfig

RNG = np.random.default_rng(5)
FMT = PositFormat(16)
B, S, H, KV, HD = 2, 67, 4, 2, 32


def _qkv(seq=S, kv_seq=None):
    kv_seq = kv_seq or seq
    q = jnp.asarray(RNG.normal(0, 1, (B, seq, H, HD)).astype(np.float32))
    k = jnp.asarray(RNG.normal(0, 1, (B, kv_seq, KV, HD)).astype(np.float32))
    v = jnp.asarray(RNG.normal(0, 1, (B, kv_seq, KV, HD)).astype(np.float32))
    return q, k, v


def _plain(q, k, v, causal, window, q_offset=0):
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    qg = q.reshape(b, sq, kv, g, hd)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / math.sqrt(hd)
    qp = q_offset + jnp.arange(sq)
    kp = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qp[:, None] >= kp[None, :]
    if window:
        mask &= qp[:, None] - kp[None, :] < window
    sc = jnp.where(mask[None, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, v)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 16)])
def test_kernel_matches_plain_attention(causal, window):
    q, k, v = _qkv()
    f = posit_flash_attention(FMT, q, k, v, causal, window, 0, 0.0,
                              "srt_r4_cs_of_fr", True, 32, 32)
    p = _plain(q, k, v, causal, window)
    # posit16 quantizes only the final o/l normalizer: ~2^-10 relative
    assert float(jnp.max(jnp.abs(f - p))) < 3e-3


def test_kernel_gqa_via_index_map():
    """Grouped heads must read the right KV block (no repeat in memory)."""
    q, k, v = _qkv()
    f = posit_flash_attention(FMT, q, k, v, True, 0, 0.0, 0.0)
    # repeat kv to full heads and run MHA: must agree exactly in structure
    kr = jnp.repeat(k, H // KV, axis=2)
    vr = jnp.repeat(v, H // KV, axis=2)
    fr = posit_flash_attention(FMT, q, kr, vr, True, 0, 0.0, 0.0)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(fr))


def test_kernel_q_offset_decode_window():
    """Cross-length q/k with q_offset (decode-style suffix query block)."""
    q, k, v = _qkv(seq=8, kv_seq=64)
    off = 56  # the 8 queries sit at positions 56..63 of the kv stream
    f = posit_flash_attention(FMT, q, k, v, True, 0, off, 0.0,
                              "srt_r4_cs_of_fr", True, 8, 16)
    p = _plain(q, k, v, True, 0, q_offset=off)
    assert float(jnp.max(jnp.abs(f - p))) < 3e-3


@pytest.mark.parametrize("n", [8, 16, 32])
def test_fully_masked_rows_normalize_to_zero(n):
    """A fully-masked query row has l == 0; the normalizer must divide by
    the format's minpos epsilon and produce 0, never 0/0 -> NaR -> NaN.
    Regression for the fixed-constant epsilon (narrow formats need a
    format-aware value; see posit_flash_attn._minpos_eps)."""
    q, k, v = _qkv(seq=8, kv_seq=8)
    # causal with a negative q_offset: every query sits before every key,
    # so all rows are fully masked
    f = posit_flash_attention(PositFormat(n), q, k, v, True, 0, -8, 0.0,
                              "srt_r4_cs_of_fr", True, 8, 8)
    out = np.asarray(f)
    assert np.isfinite(out).all(), f"NaR leaked for posit{n}"
    np.testing.assert_array_equal(out, np.zeros_like(out))


def test_partially_masked_batch_unaffected_by_eps():
    """Rows with any unmasked key have l >= 1: the minpos epsilon must not
    perturb their normalizer (bitwise vs the rowwise fused division)."""
    from repro.kernels import ops

    q, k, v = _qkv()
    f = posit_flash_attention(FMT, q, k, v, True, 0, 0, 0.0,
                              "srt_r4_cs_of_fr", True, 32, 32)
    p = _plain(q, k, v, True, 0)
    assert float(jnp.max(jnp.abs(f - p))) < 3e-3


def test_kernel_single_launch():
    from conftest import count_pallas_calls

    q, k, v = _qkv()
    assert count_pallas_calls(
        lambda q, k, v: posit_flash_attention(FMT, q, k, v), q, k, v) == 1


def test_ste_gradients_close_to_float_reference():
    q, k, v = _qkv(seq=32)
    co = jnp.asarray(RNG.normal(0, 1, (B, 32, H, HD)).astype(np.float32))

    def fused_loss(q, k, v):
        out = posit_flash_attention_ste(16, "srt_r4_cs_of_fr", True, 0, 0,
                                        0.0, q, k, v)
        return (out * co).sum()

    def ref_loss(q, k, v):
        return (_plain(q, k, v, True, 0) * co).sum()

    gf = jax.grad(fused_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=5e-3)


# ------------------------------------------------------ live-tile scan

# Each case: batch, query and key lengths, tile sizes, causal, window,
# and per-sequence kv_start / kv_len / q_pos (None: the kernel default);
# ``bs`` switches the kernel under test to the paged layout.
SCAN_CASES = {
    # decode at mixed lengths: an empty slot, kv_len 1, one at max_seq
    "decode": dict(B=5, Sq=1, Sk=64, bq=128, bk=16, causal=True, window=0,
                   kv_start=[0, 0, 3, 20, 48], kv_len=[0, 1, 18, 41, 64],
                   q_pos=[0, 0, 17, 40, 63]),
    "causal_prefill": dict(B=2, Sq=64, Sk=64, bq=16, bk=16, causal=True,
                           window=0, kv_start=[0, 21], kv_len=None,
                           q_pos=None),
    "window": dict(B=2, Sq=64, Sk=64, bq=16, bk=8, causal=True, window=12,
                   kv_start=[0, 5], kv_len=None, q_pos=None),
    "paged_decode": dict(B=4, Sq=1, Sk=64, bq=128, bk=16, causal=True,
                         window=0, kv_start=[0, 2, 16, 40],
                         kv_len=[1, 10, 34, 64], q_pos=[0, 9, 33, 63], bs=8),
}


def _scan_inputs(c, seed):
    r = np.random.default_rng(seed)
    q = r.normal(0, 1, (c["B"], c["Sq"], H, HD)).astype(np.float32)
    k = r.normal(0, 1, (c["B"], c["Sk"], KV, HD)).astype(np.float32)
    v = r.normal(0, 1, (c["B"], c["Sk"], KV, HD)).astype(np.float32)
    ks = np.asarray(c["kv_start"] or [0] * c["B"], np.int32)
    kl = np.asarray(c["kv_len"] or [c["Sk"]] * c["B"], np.int32)
    qp = np.asarray(c["q_pos"] or [0] * c["B"], np.int32)
    return q, k, v, ks, kl, qp


def _run(c, q, k, v, ks, kl, qp, sink=0.0):
    """The kernel under test: ``(out, m, l)``, (m, l) None when paged.
    The paged pools give each slot its own blocks for its written rows;
    unmapped table columns point at block 0, filled with ``sink``."""
    from repro.kernels.posit_flash_attn import _flash_call

    bt, paged = None, "bs" in c
    if paged:
        bs, cols = c["bs"], c["Sk"] // c["bs"]
        used = [-(-int(n) // bs) for n in kl]
        bt = np.zeros((c["B"], cols), np.int32)
        pk = np.full((1 + sum(used), bs, KV, HD), sink, np.float32)
        pv = pk.copy()
        nxt = 1
        for b in range(c["B"]):
            for col in range(used[b]):
                bt[b, col] = nxt
                pk[nxt] = k[b, col * bs:(col + 1) * bs]
                pv[nxt] = v[b, col * bs:(col + 1) * bs]
                nxt += 1
        k, v = pk, pv
    res = _flash_call(FMT, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      c["causal"], c["window"], 0, 0.0, "srt_r4_cs_of_fr",
                      True, c["bq"], c["bk"], 1 << 26, not paged,
                      jnp.asarray(ks), jnp.asarray(kl), jnp.asarray(qp),
                      None if bt is None else jnp.asarray(bt))
    if paged:
        return np.asarray(res), None, None
    return tuple(np.asarray(x) for x in res)


def _live_tiles(c, ks, kl, qp, b, i, bq):
    """``[lo, hi)`` of the kv tiles holding a key some real query row of
    q tile ``i`` attends, from the mask itself."""
    rows = np.arange(i * bq, min((i + 1) * bq, c["Sq"]))
    qpos = qp[b] + rows[:, None]
    kpos = np.arange(c["Sk"])[None, :]
    live = (kpos >= ks[b]) & (kpos < min(kl[b], c["Sk"]))
    if c["causal"]:
        live = live & (qpos >= kpos)
    if c["window"]:
        live = live & (qpos - kpos < c["window"])
    tiles = np.flatnonzero(live.any(0)) // c["bk"]
    return (tiles[0], tiles[-1] + 1) if tiles.size else (0, 0)


def _sliced_reference(c, q, k, v, ks, kl, qp, monkeypatch):
    """The same kernel, dense and scanning every tile it is given, with
    every (sequence, q tile) a batch row of its own whose K/V hold
    exactly its live tiles, positions shifted by the dropped prefix:
    ``(out, m, l)`` in the layout of ``_run``."""
    import repro.kernels.posit_flash_attn as FA

    B, Sq, bk = c["B"], c["Sq"], c["bk"]
    bq = min(c["bq"], -(-Sq // 8) * 8)
    nq = -(-Sq // bq)
    spans = [(b, i) + _live_tiles(c, ks, kl, qp, b, i, bq)
             for b in range(B) for i in range(nq)]
    width = max(bk, max((hi - lo) * bk for _, _, lo, hi in spans))
    n = len(spans)
    qr = np.zeros((n, bq, H, HD), np.float32)
    kr = np.zeros((n, width, KV, HD), np.float32)
    vr = np.zeros_like(kr)
    ksr, klr, qpr = (np.zeros(n, np.int32) for _ in range(3))
    for t, (b, i, lo, hi) in enumerate(spans):
        rows = q[b, i * bq:(i + 1) * bq]
        qr[t, :len(rows)] = rows
        kr[t, :(hi - lo) * bk] = k[b, lo * bk:hi * bk]
        vr[t, :(hi - lo) * bk] = v[b, lo * bk:hi * bk]
        ksr[t] = ks[b] - lo * bk
        klr[t] = (min(kl[b], c["Sk"]) - lo * bk) if hi > lo else 0
        qpr[t] = qp[b] + i * bq - lo * bk
    ref = dict(c, B=n, Sq=bq, Sk=width)
    ref.pop("bs", None)
    with monkeypatch.context() as mp:
        mp.setattr(FA, "kv_tile_bounds",
                   lambda *a, bk, sk_valid, **kw: (0, -(-sk_valid // bk)))
        o, m, l = _run(ref, qr, kr, vr, ksr, klr, qpr)
    out = np.zeros((B, nq * bq, H, HD), np.float32)
    mm = np.zeros((B * H, nq * bq), np.float32)
    ll = np.zeros_like(mm)
    for t, (b, i, _, _) in enumerate(spans):
        out[b, i * bq:(i + 1) * bq] = o[t]
        mm[b * H:(b + 1) * H, i * bq:(i + 1) * bq] = m[t * H:(t + 1) * H]
        ll[b * H:(b + 1) * H, i * bq:(i + 1) * bq] = l[t * H:(t + 1) * H]
    return out[:, :Sq], mm[:, :Sq], ll[:, :Sq]


def _same_bits(a, b):
    np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                  np.asarray(b).view(np.uint32))


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_live_tile_scan_is_exact(case, monkeypatch):
    """The kernel scans only the kv tiles a q tile can attend.  Bit for
    bit, it equals the same kernel scanning every tile of K/V cut to
    exactly those tiles, and rows outside ``[kv_start, kv_len)`` filled with NaN or the
    largest float change neither the output nor the (m, l) residuals."""
    c = SCAN_CASES[case]
    q, k, v, ks, kl, qp = _scan_inputs(c, seed=len(case))
    Sq = c["Sq"]
    out, m, l = _run(c, q, k, v, ks, kl, qp)
    ro, rm, rl = _sliced_reference(c, q, k, v, ks, kl, qp, monkeypatch)
    _same_bits(out, ro)
    if m is not None:
        _same_bits(m[:, :Sq], rm)
        _same_bits(l[:, :Sq], rl)
    dead = ((np.arange(c["Sk"])[None, :] < ks[:, None])
            | (np.arange(c["Sk"])[None, :] >= kl[:, None]))
    assert dead.any()
    for fill in (np.nan, np.finfo(np.float32).max):
        kd, vd = k.copy(), v.copy()
        kd[dead], vd[dead] = fill, fill
        o2, m2, l2 = _run(c, q, kd, vd, ks, kl, qp, sink=fill)
        _same_bits(o2, out)
        if m is not None:
            _same_bits(m2, m)
            _same_bits(l2, l)


# ------------------------------------------------------ fused backward


def test_forward_residuals_are_the_row_logsumexp():
    """The (m, l) residuals saved for the recompute backward are the row
    logsumexp in factored form: m + log(l) == logsumexp(masked scores)."""
    q, k, v = _qkv(seq=32)
    o, m, l = posit_flash_attention_fwd(FMT, q, k, v, True, 0, 0, 0.0,
                                        "srt_r4_cs_of_fr", True, 16, 16)
    o2 = posit_flash_attention(FMT, q, k, v, True, 0, 0, 0.0,
                               "srt_r4_cs_of_fr", True, 16, 16)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))
    assert m.shape == l.shape == (B * H, 32)  # O(B*H*Sq), padded rows incl.

    s = jnp.einsum("bqkgd,bskd->bkgqs",
                   q.reshape(B, 32, KV, H // KV, HD), k) / math.sqrt(HD)
    qp, kp = jnp.arange(32), jnp.arange(32)
    s = jnp.where((qp[:, None] >= kp[None, :])[None, None, None], s, -1e30)
    lse_ref = jax.scipy.special.logsumexp(s, axis=-1)      # (B, KV, G, Sq)
    lse_ref = lse_ref.reshape(B * H, 32)
    lse = m + jnp.log(l)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=0, atol=1e-5)


_BWD_TOL = {16: 5e-3, 32: 1e-5, 64: 1e-5}  # documented fused-vs-ref abs tol


@pytest.mark.parametrize("fmt_n,variant,causal,window,q_offset", [
    (16, "srt_r4_cs_of_fr", True, 0, 0),   # causal
    (16, "srt_r4_cs_of_fr", False, 0, 0),  # bidirectional
    (16, "srt_r4_cs_of_fr", True, 8, 0),   # windowed
    (16, "srt_r4_cs_of_fr", True, 0, 16),  # decode-style suffix query block
    (16, "srt_r2_cs_of_fr", True, 0, 0),   # radix-2 divider row
    (32, "srt_r4_cs_of_fr", True, 0, 0),   # wider format, same datapath
    (32, "srt_r4_scaled", True, 0, 0),     # operand scaling: 2-word frame
    (64, "srt_r4_cs_of_fr", True, 0, 0),   # posit64: two-word residual
])
def test_fused_backward_matches_reference(fmt_n, variant, causal, window,
                                          q_offset):
    """Recompute-kernel gradients vs the float-reference STE backward, on
    GQA shapes (H=4, KV=2): the mask family sweep plus Table IV divider
    rows (radix-2, operand-scaled two-word, posit64) through the W-word
    datapath plan."""
    seq = 8 if q_offset else 24
    kv_seq = q_offset + seq if q_offset else seq
    q = jnp.asarray(RNG.normal(0, 1, (B, seq, H, 16)).astype(np.float32))
    k = jnp.asarray(RNG.normal(0, 1, (B, kv_seq, KV, 16)).astype(np.float32))
    v = jnp.asarray(RNG.normal(0, 1, (B, kv_seq, KV, 16)).astype(np.float32))
    co = jnp.asarray(RNG.normal(0, 1, q.shape).astype(np.float32))

    def loss(bwd_impl):
        def f(q, k, v):
            out = posit_flash_attention_ste(
                fmt_n, variant, causal, window, q_offset, 0.0,
                q, k, v, bwd_impl)
            return (out * co).sum()
        return f

    gf = jax.grad(loss("fused"), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss("reference"), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gf, gr):
        assert bool(jnp.isfinite(a).all()), (fmt_n, name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=_BWD_TOL[fmt_n],
                                   err_msg=f"posit{fmt_n} {name}")


def test_fused_backward_fully_masked_rows_finite():
    """All-masked rows (l == 0) must produce zero gradients, not NaR/NaN."""
    q, k, v = _qkv(seq=8, kv_seq=8)
    co = jnp.ones(q.shape, jnp.float32)

    def loss(q, k, v):
        out = posit_flash_attention_ste(16, "srt_r4_cs_of_fr", True, 0, -8,
                                        0.0, q, k, v, "fused")
        return (out * co).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_array_equal(np.asarray(a), np.zeros_like(a))


def _collect_avals(jaxpr, out):
    for eqn in jaxpr.eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", None)
            if shape is not None:
                out.append(tuple(shape))
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for w in vals:
                if hasattr(w, "eqns"):                # raw Jaxpr
                    _collect_avals(w, out)
                elif hasattr(w, "jaxpr"):             # ClosedJaxpr
                    _collect_avals(w.jaxpr, out)
    return out


@pytest.mark.parametrize("bwd_impl,quadratic", [("fused", False),
                                                ("reference", True)])
def test_backward_materializes_no_score_tensor(bwd_impl, quadratic):
    """The fused backward's jaxpr must contain NO (Sq, Sk) intermediate —
    only kernel tiles (block_q/block_k sized) and O(S) residual rows.  The
    reference backward DOES materialize one (sanity check on the walk)."""
    S, big = 256, 200  # blocks are 128, so any >= (200, 200) aval is a
    #                    full score tensor, not a tile
    q = jnp.zeros((1, S, 2, 32), jnp.float32)
    k = jnp.zeros((1, S, 1, 32), jnp.float32)
    v = jnp.zeros((1, S, 1, 32), jnp.float32)

    def loss(q, k, v):
        return posit_flash_attention_ste(16, "srt_r4_cs_of_fr", True, 0, 0,
                                         0.0, q, k, v, bwd_impl).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    shapes = _collect_avals(jaxpr.jaxpr, [])
    offenders = [s for s in shapes
                 if sum(1 for d in s if d >= big) >= 2]
    if quadratic:
        assert offenders, "reference backward should materialize (Sq, Sk)"
    else:
        assert not offenders, f"(Sq, Sk) intermediates leaked: {offenders}"


# ----------------------------------------------------------- layer routing


def _fused_cfg():
    return get_config("smollm-360m", smoke=True).replace(
        attn_backend="fused",
        numerics=NumericsConfig(posit_division=True, div_backend="fused"))


def test_layer_routes_fused_attention():
    cfg = _fused_cfg()
    q = jnp.asarray(RNG.normal(0, 1, (B, 64, cfg.n_heads, cfg.head_dim))
                    .astype(np.float32))
    k = jnp.asarray(RNG.normal(0, 1, (B, 64, cfg.n_kv_heads, cfg.head_dim))
                    .astype(np.float32))
    v = jnp.asarray(RNG.normal(0, 1, (B, 64, cfg.n_kv_heads, cfg.head_dim))
                    .astype(np.float32))
    f = L.flash_attention(q, k, v, cfg, causal=True)
    x = L.flash_attention(q, k, v, cfg.replace(attn_backend="xla"),
                          causal=True)
    assert float(jnp.max(jnp.abs(f - x))) < 3e-3


def test_layer_forward_and_grad_with_fused_attention():
    cfg = _fused_cfg()
    params = L.init_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(RNG.normal(0, 1, (1, 32, cfg.d_model)).astype(np.float32))
    pos = jnp.arange(32)[None]
    out = L.attention_block(params, x, cfg, pos)
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())
    g = jax.grad(lambda x: L.attention_block(params, x, cfg, pos).sum())(x)
    assert bool(jnp.isfinite(g).all())


def test_config_rejects_fused_attn_without_fused_numerics():
    base = get_config("smollm-360m", smoke=True)
    with pytest.raises(ValueError, match="attn_backend"):
        base.replace(attn_backend="fused")
    with pytest.raises(ValueError, match="attn_backend"):
        base.replace(attn_backend="warp")
    with pytest.raises(ValueError, match="attn_backend"):
        base.replace(attn_backend="fused",
                     numerics=NumericsConfig(posit_division=True,
                                             div_backend="emulate"))
    with pytest.raises(ValueError, match="attn_bwd"):
        base.replace(attn_bwd="symbolic")


def test_layer_routes_reference_backward_flag():
    """cfg.attn_bwd='reference' keeps the float-reference STE backward
    available for A/B validation; gradients from both impls agree."""
    cfg = _fused_cfg()
    params = L.init_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(RNG.normal(0, 1, (1, 16, cfg.d_model)).astype(np.float32))
    pos = jnp.arange(16)[None]

    def g_of(c):
        return jax.grad(
            lambda x: L.attention_block(params, x, c, pos).sum())(x)

    gf = g_of(cfg)
    gr = g_of(cfg.replace(attn_bwd="reference"))
    assert bool(jnp.isfinite(gf).all())
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=0,
                               atol=5e-3)