"""Ahead-of-time compiles of every posit Pallas kernel for a TPU v5e.

Interpret mode (every other kernel test) accepts what Mosaic refuses:
bool truncations, unsigned casts, value ``dynamic_slice``s, untileable
block shapes, VMEM over budget.  These tests lower each kernel with
``interpret=False`` for a described ``v5e:2x2`` topology at SmolLM-360M
widths (15 q / 5 kv heads, head_dim 64, d_model 960, 2048-row KV) and
assert that the compiled program holds the Mosaic kernel.  Nothing runs,
so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.posit import PositFormat
from repro.kernels import ops
from repro.kernels.posit_div import DEFAULT_KERNEL_VARIANT as VARIANT
from repro.kernels.posit_flash_attn import (
    _flash_backward,
    posit_flash_attention,
    posit_flash_attention_fwd,
)

P16, P32 = PositFormat(16), PositFormat(32)
H, KV, HD, D_MODEL, MAX_SEQ, SLOTS = 15, 5, 64, 960, 2048, 8


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return hlo.count('custom_call_target="tpu_custom_call"')


F32, U32, I32 = jnp.float32, jnp.uint32, jnp.int32

# name -> (fn, arg shapes, kernels expected in the compiled program)
CASES = {
    "divide_posit16": (
        lambda x, d: ops.posit_div(P16, x, d, interpret=False),
        [((SLOTS * H, MAX_SEQ), U32)] * 2, 1),
    "divide_posit32": (
        lambda x, d: ops.posit_div(P32, x, d, interpret=False),
        [((SLOTS * H, MAX_SEQ), U32)] * 2, 1),
    "fused_elementwise": (
        lambda a, b: ops.posit_div_fused(P16, a, b, interpret=False),
        [((SLOTS * H, MAX_SEQ), F32)] * 2, 1),
    # RMSNorm: one reciprocal per token row of width d_model
    "fused_rowwise": (
        lambda a, b: ops.posit_div_fused_rowwise(P16, a, b, interpret=False),
        [((512, D_MODEL), F32), ((512, 1), F32)], 1),
    # decode-attention softmax over every slot's whole KV row
    "fused_softmax": (
        lambda x: ops.posit_softmax_fused(P16, x, interpret=False),
        [((SLOTS * H, MAX_SEQ), F32)], 1),
    "quantize": (
        lambda x: ops.posit_quantize(P16, x, interpret=False),
        [((512, D_MODEL), F32)], 1),
    "dequantize": (
        lambda p: ops.posit_dequantize(P32, p, interpret=False),
        [((512, D_MODEL), U32)], 1),
    "flash_prefill": (
        lambda q, k, v, s: posit_flash_attention(
            P16, q, k, v, True, 0, 0, 0.0, VARIANT, False, kv_start=s),
        [((1, 512, H, HD), F32), ((1, 512, KV, HD), F32),
         ((1, 512, KV, HD), F32), ((1,), I32)], 1),
    "flash_decode": (
        lambda q, k, v, s, p: posit_flash_attention(
            P16, q, k, v, True, 0, 0, 0.0, VARIANT, False, kv_start=s,
            kv_len=p + 1, q_pos=p),
        [((SLOTS, 1, H, HD), F32), ((SLOTS, MAX_SEQ, KV, HD), F32),
         ((SLOTS, MAX_SEQ, KV, HD), F32), ((SLOTS,), I32),
         ((SLOTS,), I32)], 1),
    "flash_decode_paged": (
        lambda q, k, v, bt, p: posit_flash_attention(
            P16, q, k, v, True, 0, 0, 0.0, VARIANT, False, kv_len=p + 1,
            q_pos=p, block_tables=bt),
        [((SLOTS, 1, H, HD), F32), ((33, 16, KV, HD), F32),
         ((33, 16, KV, HD), F32), ((SLOTS, 4), I32), ((SLOTS,), I32)], 1),
    "flash_prefill_packed": (
        lambda q, k, v, sq, skv: posit_flash_attention(
            P16, q, k, v, True, 0, 0, 0.0, VARIANT, False, seg_q=sq,
            seg_kv=skv),
        [((1, 512, H, HD), F32), ((1, 512, KV, HD), F32),
         ((1, 512, KV, HD), F32), ((1, 512), I32), ((1, 512), I32)], 1),
    # sliding-window attention (the hybrid family's local layers): the
    # scan's lower tile bound follows the window
    "flash_prefill_window": (
        lambda q, k, v: posit_flash_attention(
            P16, q, k, v, True, 128, 0, 0.0, VARIANT, False),
        [((1, 512, H, HD), F32), ((1, 512, KV, HD), F32),
         ((1, 512, KV, HD), F32)], 1),
    "flash_forward_residuals": (
        lambda q, k, v: posit_flash_attention_fwd(
            P16, q, k, v, True, 0, 0, 0.0, VARIANT, False),
        [((1, 256, H, HD), F32), ((1, 256, KV, HD), F32),
         ((1, 256, KV, HD), F32)], 1),
    # the dq kernel and the dk/dv kernel
    "flash_backward": (
        lambda q, k, v, o, g, m, l: _flash_backward(
            P16, q, k, v, o, g, m, l, True, 0, 0, 0.0, VARIANT, False),
        [((1, 256, H, HD), F32), ((1, 256, KV, HD), F32),
         ((1, 256, KV, HD), F32), ((1, 256, H, HD), F32),
         ((1, 256, H, HD), F32), ((H, 256), F32), ((H, 256), F32)], 2),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes, kernels = CASES[name]
    assert _compile(fn, one_chip, *shapes) == kernels


def test_paged_pool_over_vmem_budget_is_refused():
    """The paged kernel keeps whole pools in VMEM: a serving-size pool is
    refused with a message, before Mosaic would run out of memory."""
    q = jax.ShapeDtypeStruct((SLOTS, 1, H, HD), F32)
    pool = jax.ShapeDtypeStruct((SLOTS * 128 + 1, 16, KV, HD), F32)
    bt = jax.ShapeDtypeStruct((SLOTS, 128), I32)
    with pytest.raises(ValueError, match="whole KV block pools in VMEM"):
        jax.eval_shape(
            lambda q, k, v, bt: posit_flash_attention(
                P16, q, k, v, True, 0, 0, 0.0, VARIANT, False,
                block_tables=bt), q, pool, pool, bt)
