#!/usr/bin/env python3
"""Bring-up smoke test of the posit serving stack on a TPU.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --chips 4         # the four-chip paths only

Everything runs in this one process, and all data comes from ``--seed``.
Each phase raises on failure, so the exit code is nonzero and no result
line is printed; there is no CPU fallback.

One chip:
  device   JAX's first device must be a TPU.
  kernels  every Pallas kernel, compiled (``interpret=False``) on the chip,
           against the emulate path run on the host CPU in this process:
           the pattern divider (posit16, posit32), the fused elementwise,
           rowwise and softmax dividers, quantize and dequantize, and flash
           attention at SmolLM-360M prefill and decode shapes.  Operands
           include 0, NaR, minpos, maxpos and f32 subnormals.  Outputs must
           be bit-identical.  Softmax and flash also run random inputs,
           where TPU and CPU ``exp``/``dot`` differ in the last bits, and
           those must agree within a tolerance.
  serve    SmolLM-360M at its published widths (random weights from the
           seed) on the continuous-batching engine with the SRT divider in
           the model (``get_config(..., fused=True)``), dense KV: 16 greedy
           requests of 16-512 prompt tokens and 32 new tokens each.  Every
           request must finish EOS or MAX_NEW, and the decode executable
           must hold compiled kernels.  Correctness: an engine with the
           fused divider and XLA attention decodes two of the requests with
           every model division also run through the emulate divider on
           the same operands, and all quotients must be bit-identical; and
           the fused engine's prefill logits match a float32 forward without
           posit numerics.

Four chips (``--chips 4``):
  replicas ``ReplicaRouter`` over four one-chip SmolLM-360M replicas, each
           on its own device; every request's tokens equal a single
           engine's.
  tp       granite-8b at published widths, cut to 4 layers, served
           tensor-parallel over the four chips with ``tp_groups=4``; tokens
           bit-identical to the unsharded one-chip engine.

The last line of standard output is the JSON result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Fused-engine prefill logits vs the float32 reference, as
# ||model - ref||_2 / ||ref||_2 over the real vocab at the last prompt
# position.  The model computes in bfloat16, so the gap is bfloat16
# rounding, not posit division.  Measured on the CPU at SmolLM-360M depth
# (32 layers) and heads (15/5), width cut to d_model 240: 0.020-0.025 for
# both the fused and the float-division model, 0.012-0.017 at 2 layers.
# The limit leaves 2.4x headroom over the worst of those.
PREFILL_REL_L2_TOL = 0.06
# Random-input softmax/flash on TPU vs CPU: exp and the f32 dot products
# differ in the last bits between the two backends, which can move an
# output by a posit16 rounding step (2^-12 relative) or a little more.
RANDOM_ABS_TOL = 2e-2


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(*a):
    print(*a, flush=True)


# =====================================================================
# device
# =====================================================================


def device_phase():
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    log(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    return devs


# =====================================================================
# kernels
# =====================================================================


def _bits(a):
    """uint32 view for bit comparison; every NaN (NaR) maps to one pattern."""
    a = np.asarray(a)
    if a.dtype == np.float32:
        b = a.view(np.uint32).copy()
        b[np.isnan(a)] = 0x7FC00000
        return b
    return a.astype(np.uint32)


def _float_operands(rng, shape, fmt):
    """Log-uniform magnitudes past the posit range, random signs, and the
    special values at the front."""
    x = np.exp2(rng.uniform(-fmt.max_scale - 10,
                            min(fmt.max_scale + 10, 127), shape))
    x = (x * rng.choice([-1.0, 1.0], shape)).astype(np.float32)
    minpos, maxpos = 2.0 ** -fmt.max_scale, 2.0 ** fmt.max_scale
    specials = np.array(
        [0.0, -0.0, np.nan, np.inf, -np.inf, minpos, -minpos, maxpos,
         -maxpos, minpos / 2, maxpos * 2, 2.0 ** -149, 1e-40, -3e-39, 1.0,
         3.0, 1.0 / 3.0], np.float32)
    flat = x.reshape(-1)
    flat[:specials.size] = specials
    flat[specials.size:2 * specials.size] = rng.permutation(specials)
    return flat.reshape(shape)


def _pattern_operands(rng, shape, fmt):
    p = rng.integers(0, 1 << fmt.n, shape, dtype=np.uint64).astype(np.uint32)
    specials = np.array([0, fmt.nar_pattern, 1, fmt.maxpos_body,
                         fmt.mask, fmt.nar_pattern + 1, 1 << (fmt.n - 2)],
                        np.uint32)
    flat = p.reshape(-1)
    flat[:specials.size] = specials
    flat[specials.size:2 * specials.size] = rng.permutation(specials)
    return flat.reshape(shape)


def kernel_phase(seed, tpu, cpu, *, slots=8, max_seq=2048, prompt=512,
                 heads=(15, 5, 64), d_model=960, interpret=False):
    """Every kernel on ``tpu`` (compiled unless ``interpret``) against the
    emulate path on ``cpu``; returns the names checked."""
    import jax

    from repro.core.divider import posit_divide
    from repro.core.posit import POSIT16, POSIT32, float_to_posit, posit_to_float
    from repro.kernels import ops
    from repro.kernels.posit_div import DEFAULT_KERNEL_VARIANT as VARIANT
    from repro.kernels.posit_flash_attn import posit_flash_attention
    from repro.numerics import NumericsConfig
    from repro.numerics.posit_ops import posit_softmax

    rng = np.random.default_rng(seed)
    H, KV, hd = heads
    checked = []

    def on(dev, *xs):
        return [jax.device_put(x, dev) for x in xs]

    def same(name, got, want):
        got, want = _bits(got), _bits(want)
        bad = int(np.count_nonzero(got != want))
        check(got.shape == want.shape and bad == 0,
              f"{name}: {bad}/{want.size} outputs differ from the emulate "
              "path")
        checked.append(name)
        log(f"[kernels] {name}: {want.size} outputs bit-identical")

    def close(name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        err = float(np.max(np.abs(got - want)))
        bits = int(np.count_nonzero(_bits(got) != _bits(want)))
        check(np.isfinite(got).all() and err <= RANDOM_ABS_TOL,
              f"{name}: max |tpu - cpu| = {err} > {RANDOM_ABS_TOL}")
        checked.append(name)
        log(f"[kernels] {name}: max |tpu - cpu| = {err!r} "
            f"({bits}/{want.size} outputs differ in bits)")

    def emulate_div(fmt):
        return jax.jit(lambda a, b: posit_to_float(fmt, posit_divide(
            fmt, float_to_posit(fmt, a), float_to_posit(fmt, b), VARIANT)))

    rows = slots * H
    for fmt in (POSIT16, POSIT32):
        px = _pattern_operands(rng, (rows, max_seq), fmt)
        pd = _pattern_operands(rng, (rows, max_seq), fmt)
        got = ops.posit_div(fmt, *on(tpu, px, pd), interpret=interpret)
        want = jax.jit(lambda x, d, f=fmt: posit_divide(f, x, d, VARIANT))(
            *on(cpu, px, pd))
        same(f"divide_{fmt}", got, want)

        a = _float_operands(rng, (rows, max_seq), fmt)
        b = _float_operands(rng, (rows, max_seq), fmt)
        got = ops.posit_div_fused(fmt, *on(tpu, a, b), interpret=interpret)
        same(f"fused_elementwise_{fmt}", got, emulate_div(fmt)(*on(cpu, a, b)))

        x = _float_operands(rng, (prompt, d_model), fmt)
        got = ops.posit_quantize(fmt, *on(tpu, x), interpret=interpret)
        same(f"quantize_{fmt}", got,
             jax.jit(lambda v, f=fmt: float_to_posit(f, v))(*on(cpu, x)))
        p = _pattern_operands(rng, (prompt, d_model), fmt)
        got = ops.posit_dequantize(fmt, *on(tpu, p), interpret=interpret)
        same(f"dequantize_{fmt}", got,
             jax.jit(lambda v, f=fmt: posit_to_float(f, v))(*on(cpu, p)))

    # RMSNorm shape: one divisor per token row of width d_model
    a = _float_operands(rng, (prompt, d_model), POSIT16)
    b = _float_operands(rng, (prompt, 1), POSIT16)
    got = ops.posit_div_fused_rowwise(POSIT16, *on(tpu, a, b),
                                      interpret=interpret)
    same("fused_rowwise_posit16", got,
         emulate_div(POSIT16)(*on(cpu, a, np.broadcast_to(b, a.shape))))

    # softmax over every slot's KV row.  Exact rows: each lane is the row
    # max or 1e4 below it, so exp is exactly 1 or 0 on any backend and the
    # row sum and SRT divide alone decide the bits.
    emu = NumericsConfig(posit_division=True, div_backend="emulate")
    soft_cpu = jax.jit(lambda v: posit_softmax(v, emu))
    x = np.where(rng.random((rows, max_seq)) < rng.random((rows, 1)),
                 0.0, -1e4).astype(np.float32) + rng.normal(0, 3, (rows, 1)
                                                            ).astype(np.float32)
    x[:, 0] = x[:, 1:].max(axis=1)
    got = ops.posit_softmax_fused(POSIT16, *on(tpu, x), interpret=interpret)
    same("fused_softmax_posit16", got, soft_cpu(*on(cpu, x)))
    x = rng.normal(0, 4, (rows, max_seq)).astype(np.float32)
    got = ops.posit_softmax_fused(POSIT16, *on(tpu, x), interpret=interpret)
    close("fused_softmax_posit16_random", got, soft_cpu(*on(cpu, x)))

    # flash attention: the same kernel interpreted on the CPU.  Exact
    # inputs: q = 0 makes every score 0, so exp is exactly 1 and the
    # output is (sum of the unmasked v rows) / count through the SRT
    # normalizer; v holds small dyadic values whose sums are exact.
    fmt = POSIT16

    def flash(dev, q, k, v, **kw):
        args = on(dev, q, k, v)
        kw = {n: jax.device_put(a, dev) for n, a in kw.items()}
        return posit_flash_attention(
            fmt, *args, True, 0, 0, 0.0, VARIANT,
            interpret if dev is tpu else True, **kw)

    def qkv(B, Sq, Sk, exact):
        if exact:
            q = np.zeros((B, Sq, H, hd), np.float32)
            v = rng.integers(-16, 17, (B, Sk, KV, hd)).astype(np.float32) / 8
        else:
            q = rng.normal(0, 1, (B, Sq, H, hd)).astype(np.float32)
            v = rng.normal(0, 1, (B, Sk, KV, hd)).astype(np.float32)
        k = rng.normal(0, 1, (B, Sk, KV, hd)).astype(np.float32)
        return q, k, v

    pos = rng.integers(0, max_seq, slots).astype(np.int32)
    pos[:2] = (0, max_seq - 1)
    start = (pos * rng.random(slots)).astype(np.int32)
    for exact in (True, False):
        tag = "" if exact else "_random"
        cmp = same if exact else close
        q, k, v = qkv(1, prompt, prompt, exact)
        kw = dict(kv_start=np.array([prompt // 7], np.int32))
        cmp(f"flash_prefill{tag}", flash(tpu, q, k, v, **kw),
            flash(cpu, q, k, v, **kw))
        q, k, v = qkv(slots, 1, max_seq, exact)
        kw = dict(kv_start=start, kv_len=pos + 1, q_pos=pos)
        cmp(f"flash_decode{tag}", flash(tpu, q, k, v, **kw),
            flash(cpu, q, k, v, **kw))
    return checked


# =====================================================================
# serve
# =====================================================================


def _init_params(cfg, seed):
    """Random weights from ``seed``, made in one compiled program."""
    import jax

    from repro.models import transformer as T

    return jax.jit(T.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))


def _requests(rng, n, vocab, lo, hi, max_new):
    from repro.serve import Request

    return [Request(rng.integers(1, vocab, int(rng.integers(lo, hi + 1))
                                 ).astype(np.int32), max_new=max_new)
            for _ in range(n)]


def _stream(eng, reqs):
    """Submit ``reqs`` and drain the stream; returns results by rid."""
    from repro.serve import FinishEvent

    for r in reqs:
        eng.submit(r)
    return {ev.rid: ev.result for ev in eng.serve_stream()
            if isinstance(ev, FinishEvent)}


def _check_finished(results, n, what):
    from repro.serve import FinishReason

    check(sorted(results) == list(range(n)),
          f"{what}: {len(results)} of {n} requests finished")
    bad = {rid: r.finish.value for rid, r in results.items()
           if r.finish not in (FinishReason.EOS, FinishReason.MAX_NEW)}
    check(not bad, f"{what}: requests finished abnormally: {bad}")


@contextlib.contextmanager
def _both_dividers():
    """While open, every model division runs both dividers on the same
    operands, returns the fused quotient, and adds to the yielded
    ``[quotients, mismatches]`` tally from the device."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import layers
    from repro.numerics import posit_ops

    tally = [0, 0]
    div = posit_ops.posit_div_values

    def record(n, bad):
        tally[0] += int(n)
        tally[1] += int(bad)

    def both(a, b, cfg):
        got = div(a, b, cfg)
        want = div(a, b, dataclasses.replace(cfg, div_backend="emulate"))
        bits = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa: E731
        bad = (bits(got) != bits(want)) & ~(jnp.isnan(got) & jnp.isnan(want))
        jax.debug.callback(record, got.size, jnp.sum(bad, dtype=jnp.int32))
        return got

    posit_ops.posit_div_values = layers.posit_div_values = both
    try:
        yield tally
    finally:
        posit_ops.posit_div_values = layers.posit_div_values = div


def serve_phase(seed, dev, *, arch="smollm-360m", smoke=False, n_req=16,
                plen=(16, 512), max_new=32, max_batch=8, max_seq=2048):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.reference import reference_logits
    from repro.numerics import NumericsConfig
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.engine import _bucket

    rng = np.random.default_rng(seed)
    cfg = get_config(arch, smoke=smoke, fused=True, max_batch=max_batch,
                     max_seq=max_seq)
    t0 = time.perf_counter()
    params = _init_params(cfg, seed)
    jax.block_until_ready(params)
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab}, "
        f"{n_params} params, init {time.perf_counter() - t0:.2f}s")
    reqs = _requests(rng, n_req, cfg.vocab, *plen, max_new)
    buckets = sorted({_bucket(len(r.tokens), max_seq) for r in reqs})

    eng = ServeEngine(cfg, params, ServeConfig.from_model(cfg))
    t0 = time.perf_counter()
    census = eng.warmup(prompt_lens=buckets)
    log(f"[serve] compile+warmup {time.perf_counter() - t0:.2f}s for "
        f"prompt buckets {buckets}")

    results = _stream(eng, reqs)
    _check_finished(results, n_req, "fused engine")
    log(f"[serve] {n_req} requests; finish "
        f"{dict(eng.last_serve_stats['finish_reasons'])}")
    counts = eng.executable_counts()
    log(f"[serve] executable_counts {counts}"
        + ("" if counts == census else f"; after warmup it was {census}"))

    B = max_batch
    sds = jax.ShapeDtypeStruct
    hlo = eng._decode.lower(
        eng.params,
        jax.eval_shape(lambda: T.init_cache(cfg, B, max_seq)),
        sds((B, 1), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32)).as_text()
    n_kernels = hlo.count("tpu_custom_call")
    check(n_kernels > 0, "decode executable holds no compiled Pallas kernel")
    log(f"[serve] decode executable: {n_kernels} tpu_custom_call sites")

    # correctness 1: the fused divider against the emulate divider on the
    # operands the model really divides.  Two engines cannot be compared
    # token for token on a TPU: XLA rounds the bfloat16 activations around
    # the divider differently in two different programs.
    ab = sorted(range(n_req), key=lambda i: len(reqs[i].tokens))[:2]
    c = get_config(arch, smoke=smoke, max_batch=max_batch,
                   max_seq=max_seq).replace(numerics=NumericsConfig(
                       posit_division=True, div_backend="fused"))
    t0 = time.perf_counter()
    with _both_dividers() as tally:
        e = ServeEngine(c, params, ServeConfig.from_model(c))
        res = _stream(e, [reqs[i] for i in ab])
        jax.effects_barrier()
    _check_finished(res, len(ab), "fused-divider engine")
    n_div, n_bad = tally[0], tally[1]
    check(n_div > 0 and n_bad == 0,
          f"fused divider differs from the emulate divider in {n_bad} of "
          f"{n_div} model quotients")
    log(f"[serve] fused-divider engine (xla attention), requests {ab}: "
        f"{n_div} model quotients bit-identical to the emulate divider "
        f"({time.perf_counter() - t0:.2f}s incl. compile)")

    # correctness 2: fused prefill logits vs the float32 reference
    prefill = jax.jit(lambda p, c, t, s: T.prefill(p, cfg, {"tokens": t},
                                                   c, s))
    ref = jax.jit(lambda p, t: reference_logits(p, cfg, t)[:, -1])
    for i in (ab[0], max(range(n_req), key=lambda i: len(reqs[i].tokens))):
        t = jnp.asarray(reqs[i].tokens[None])
        lg, _ = prefill(eng.params, T.init_cache(cfg, 1, max_seq), t,
                        jnp.zeros((1,), jnp.int32))
        got = np.asarray(lg[0, -1, :cfg.vocab], np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref(eng.params, t)[0])
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        log(f"[serve] prefill logits vs f32 reference, {t.shape[1]} "
            f"tokens: rel L2 {rel!r}, argmax {int(got.argmax())} vs "
            f"{int(want.argmax())}")
        check(np.isfinite(got).all() and rel <= PREFILL_REL_L2_TOL,
              f"prefill logits rel L2 {rel} > {PREFILL_REL_L2_TOL}")

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"[serve] peak_bytes_in_use {peak}")


# =====================================================================
# four chips
# =====================================================================


def replica_phase(seed, devs, *, arch="smollm-360m", smoke=False, n_req=8,
                  plen=(17, 128), max_new=32, max_batch=8, max_seq=2048):
    """Four one-chip replicas behind the router vs one engine."""
    import jax

    from repro.configs import get_config
    from repro.serve import ReplicaRouter, ServeConfig, ServeEngine

    rng = np.random.default_rng(seed)
    cfg = get_config(arch, smoke=smoke, fused=True, max_batch=max_batch,
                     max_seq=max_seq)
    params = _init_params(cfg, seed)
    sc = ServeConfig.from_model(cfg)
    reqs = _requests(rng, n_req, cfg.vocab, *plen, max_new)

    t0 = time.perf_counter()
    single = _stream(ServeEngine(cfg, params, sc, device=devs[0]), reqs)
    _check_finished(single, n_req, "single engine")
    log(f"[replicas] single engine: {time.perf_counter() - t0:.2f}s incl. "
        "compile")
    engines = [ServeEngine(cfg, params, sc, device=d) for d in devs]
    placed = [{d for a in jax.tree.leaves(e.params) for d in a.devices()}
              for e in engines]
    check(all(p == {d} for p, d in zip(placed, devs)),
          f"replica params not each on its own device: {placed}")
    router = ReplicaRouter(engines, policy="round_robin")
    t0 = time.perf_counter()
    routed = _stream(router, reqs)
    _check_finished(routed, n_req, "router")
    for e, d in zip(engines, devs):
        cache = {x for a in jax.tree.leaves(e._st.cache) for x in a.devices()}
        check(cache == {d}, f"replica cache on {cache}, not {d}")
    diff = [i for i in range(n_req)
            if routed[i].tokens.tolist() != single[i].tokens.tolist()]
    check(not diff, f"router tokens differ from one engine for {diff}")
    log(f"[replicas] {len(devs)} replicas on {sorted(d.id for d in devs)}: "
        f"{n_req} requests identical to one engine "
        f"({time.perf_counter() - t0:.2f}s incl. compile)")


def tp_phase(seed, devs, *, arch="granite-8b", smoke=False, n_layers=4,
             n_req=4, plen=(16, 64), max_new=16, max_batch=4, max_seq=512):
    """TP=len(devs) engine vs the unsharded one-chip engine."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import serve_meshes
    from repro.serve import ServeConfig, ServeEngine

    tp = len(devs)
    rng = np.random.default_rng(seed)
    cfg = get_config(arch, smoke=smoke, fused=True, max_batch=max_batch,
                     max_seq=max_seq).replace(n_layers=n_layers,
                                              tp_groups=tp)
    with jax.default_device(devs[0]):
        params = _init_params(cfg, seed)
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    sc = ServeConfig.from_model(cfg)
    reqs = _requests(rng, n_req, cfg.vocab, *plen, max_new)

    t0 = time.perf_counter()
    one = _stream(ServeEngine(cfg, params, sc, device=devs[0]), reqs)
    _check_finished(one, n_req, "unsharded engine")
    log(f"[tp] {cfg.name} x{n_layers} layers ({n_params} params) unsharded: "
        f"{time.perf_counter() - t0:.2f}s incl. compile")
    mesh = serve_meshes(tp, 1, devices=devs)[0]
    t0 = time.perf_counter()
    sharded = _stream(ServeEngine(cfg, params, sc, mesh=mesh), reqs)
    _check_finished(sharded, n_req, "tp engine")
    diff = [i for i in range(n_req)
            if sharded[i].tokens.tolist() != one[i].tokens.tolist()]
    check(not diff, f"TP={tp} tokens differ from unsharded for {diff}")
    log(f"[tp] TP={tp} tokens bit-identical to unsharded for {n_req} "
        f"requests ({time.perf_counter() - t0:.2f}s incl. compile)")


# =====================================================================


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip paths (replicas, TP)")
    args = ap.parse_args(argv)

    # the emulate references run on the host CPU in this process, so the
    # CPU backend must come up next to the TPU (which stays first)
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    devs = device_phase()
    count = len(devs)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[device] compile cache {enable_compile_cache()}")
    t_start = time.perf_counter()
    if args.chips == 4:
        check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
        devs = devs[:4]
        replica_phase(args.seed, devs)
        tp_phase(args.seed, devs)
    else:
        import jax

        kernel_phase(args.seed, devs[0], jax.devices("cpu")[0])
        serve_phase(args.seed, devs[0])
    log(f"[done] {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
