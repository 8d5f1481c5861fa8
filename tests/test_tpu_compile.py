"""Every Pallas kernel compiles for a TPU v5e at qwen3-moe-30b-a3b widths.

No chip is needed: the TPU compiler compiles for a described (not attached)
``v5e:2x2`` topology, and refuses what the chip would refuse (block shapes
off the (8, 128) tiling, too much VMEM).  The topology is described inside
a fixture, never at import time: only one process at a time may load the
TPU library, and these tests stay in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch

CFG = get_arch("qwen3-moe-30b-a3b")
D, F, E = CFG.d_model, CFG.moe.d_ff_expert, CFG.moe.n_experts
HQ, HKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.hd
TOKENS, CAP, SEQ = 512, 64, 1024
ROWS = TOKENS * CFG.moe.top_k


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernels():
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.fused_staging import fused_swiglu_pallas
    from repro.kernels.grouped_matmul import grouped_matmul
    from repro.kernels.segment_gather import segment_gather
    from repro.kernels.segment_scatter_add import segment_scatter_add

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32

    def flash(q, k, v, qp, kp):
        return flash_attention(q, k, v, qp, kp, True, None, 512, 512)

    def flash_loss(q, k, v, qp, kp):
        return flash(q, k, v, qp, kp).astype(f32).sum()

    attn = [((2, SEQ // 2, HQ, HD), bf), ((2, SEQ, HKV, HD), bf),
            ((2, SEQ, HKV, HD), bf), ((SEQ // 2,), i32), ((SEQ,), i32)]
    return {
        "segment_gather": (segment_gather,
                           [((TOKENS, D), bf), ((ROWS,), i32)]),
        "segment_scatter_add": (
            lambda s, d, g: segment_scatter_add(s, d, g, TOKENS),
            [((ROWS, D), bf), ((ROWS,), i32), ((ROWS,), f32)]),
        "fused_swiglu": (fused_swiglu_pallas,
                         [((1, E, CAP, D), bf), ((E, D, F), bf),
                          ((E, D, F), bf), ((E, F, D), bf), ((1, E), i32)]),
        "grouped_matmul": (grouped_matmul,
                           [((E, CAP, D), bf), ((E, D, F), bf), ((E,), i32)]),
        "flash_attention": (flash, attn),
        "flash_attention_vjp": (jax.grad(flash_loss, argnums=(0, 1, 2)),
                                attn),
    }


@pytest.mark.parametrize("name", ["segment_gather", "segment_scatter_add",
                                  "fused_swiglu", "grouped_matmul",
                                  "flash_attention", "flash_attention_vjp"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernels()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
