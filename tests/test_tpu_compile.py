"""Kernels of the served path compiled for the chip, without the chip: the
TPU compiler is installed here and compiles for a DESCRIBED v5e, so what
Mosaic would refuse on the machine (a slice off the tiling, too much VMEM,
an op with no lowering) fails here at no chip time. Nothing runs: a compile
that passes says nothing about results or times.

The topology is described inside a fixture, never at import (only one
process may load the TPU library; see the on-chip-measurement guide), and
every such test lives in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.kernels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# B, H, Hkv, Dh, pages a row, side window, pool dtype
@pytest.mark.parametrize("b,h,hkv,dh,mp,w,kv_dtype", [
    (8, 32, 8, 128, 8, 8, jnp.bfloat16),      # mistral-7b cells, full chunk
    (8, 32, 8, 128, 8, 1, jnp.bfloat16),      # a chunk cut to one step
    (8, 32, 8, 128, 8, 5, jnp.bfloat16),      # ... at max_seq_len's edge
    (8, 28, 4, 128, 8, 8, jnp.bfloat16),      # qwen2-7b: 7 heads a KV head
    (128, 32, 8, 128, 2, 8, jnp.bfloat16),    # chip_smoke's 128 slots
    (8, 32, 8, 128, 8, 8, jnp.float8_e4m3fn),
])
def test_flash_decode_compiles_for_v5e(one_chip, b, h, hkv, dh, mp, w,
                                       kv_dtype):
    from distributed_inference_engine_tpu.ops.flash_decode import (
        flash_decode_attention_pallas)

    layers, n, p = 2, b * mp, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, kp, vp, pt, plen, sk, sv, n_side, layer):
        return flash_decode_attention_pallas(
            q, kp, vp, pt, plen, sk, sv, n_side, n_kv_heads=hkv,
            layer=layer, n_pages_per_layer=n)

    bf = jnp.bfloat16
    compiled = jax.jit(fn).lower(
        sds((b, h, dh), bf), sds((layers * n, p, hkv * dh), kv_dtype),
        sds((layers * n, p, hkv * dh), kv_dtype), sds((b, mp), jnp.int32),
        sds((b,), jnp.int32), sds((b, w, hkv, dh), bf),
        sds((b, w, hkv, dh), bf), sds((b,), jnp.int32),
        sds((), jnp.int32)).compile()
    assert "flash_decode_custom_call" in compiled.as_text()


# the five payload shapes of the mistral-7b cells, through the blocks the
# kernel resolves itself (ops.int4_matmul.blocks_for): rows 8 = a served
# decode step (the decode bucket), rows 256 / 768 = its prefill programs
@pytest.mark.parametrize("rows,k2,n", [
    (8, 2048, 6144), (8, 2048, 4096), (8, 2048, 28672), (8, 7168, 4096),
    (8, 2048, 32768), (1, 2048, 4096), (16, 7168, 4096),
    (256, 2048, 6144), (768, 2048, 28672), (768, 7168, 4096),
])
def test_int4_matmul_compiles_for_v5e(one_chip, rows, k2, n):
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        _int4_matmul_stacked)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(_int4_matmul_stacked).lower(
        sds((rows, 2 * k2), jnp.bfloat16), sds((2, k2, n), jnp.int8),
        sds((2, 1, n), jnp.float32), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the benchmark counts decode steps as device ops named ``int4``: one
    # a matmul, whatever the schedule
    names = [ln.split(" = ")[0] for ln in text.splitlines()
             if " = " in ln and "%" in ln.split(" = ")[0]
             and "int4" in ln.split(" = ")[0]]
    assert len(names) == 1, names


# the latent prefill at the published head shape (32 heads of 128 | 64 |
# 128): the smallest and largest buckets of the Ling cell (512, 2048) and
# of the Xing cell (1024, 8192, 8704 = 17 blocks), and two rows at once
@pytest.mark.parametrize("b,t", [(1, 512), (1, 1024), (1, 2048), (1, 8192),
                                 (1, 8704), (2, 1024)])
def test_mla_prefill_compiles_for_v5e(one_chip, b, t):
    from distributed_inference_engine_tpu.ops import mla

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *a: mla.mla_causal_attention(
        *a, impl="flash")).lower(
            sds((b, t, 32, 128)), sds((b, t, 32, 64)), sds((b, t, 32, 256)),
            sds((b, t, 64)), sds((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "mla_prefill_flash" in text
    # no score tensor in HBM: nothing float32 as large as [32, 512, t]
    assert f"f32[{b},32,512," not in text and f"f32[32,512,{t}]" not in text
