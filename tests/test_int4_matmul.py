"""Mosaic int4-unpack matmul kernel (ops/int4_matmul.py) — interpret-mode
correctness on CPU; the perf claim lives in README/BENCH (measured on the
real chip, where this kernel is the default int4 path on single-device
processes).

The kernel math must match quantize->dequantize->einsum exactly in
structure (same contraction, fp32 accumulation): tolerance covers only
dot-order noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.ops import quant
from distributed_inference_engine_tpu.ops.int4_matmul import (
    _int4_matmul_2d,
    kernel_wants,
    set_kernel_mode,
)


@pytest.fixture
def kernel_on():
    set_kernel_mode("on")
    yield
    set_kernel_mode("auto")


def _q4(rs, k, n):
    w = jnp.asarray(rs.randn(k, n).astype("float32") * 0.05)
    return w, quant.quantize_weight(w, (0,), bits=4)


@pytest.mark.parametrize("m,k,n", [(5, 256, 256), (64, 512, 384),
                                   (16, 256, 128)])
def test_kernel_matches_dequantized_reference(m, k, n):
    rs = np.random.RandomState(m + k + n)
    w, qt = _q4(rs, k, n)
    x = jnp.asarray(rs.randn(m, k).astype("float32"))
    ref = jnp.einsum("md,df->mf", x, qt.dequantize(jnp.float32))
    got = _int4_matmul_2d(x, qt.q, qt.s.astype(jnp.float32), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_bf16_activations_exact_vs_fp32_dot():
    """int4 values and bf16 activations are both exact in the fp32-
    accumulated dot — the kernel must agree with the fp32 reference run
    on the SAME bf16 inputs, bit-for-bit after the output cast."""
    rs = np.random.RandomState(0)
    w, qt = _q4(rs, 256, 256)
    x = jnp.asarray(rs.randn(32, 256).astype("float32")).astype(jnp.bfloat16)
    ref = (jnp.einsum("md,df->mf", x.astype(jnp.float32),
                      qt.dequantize(jnp.float32))).astype(jnp.bfloat16)
    got = _int4_matmul_2d(x, qt.q, qt.s.astype(jnp.float32), interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype="float32"), np.asarray(ref, dtype="float32"),
        rtol=1e-2, atol=1e-2)


def test_matmul_any_dispatches_to_kernel(kernel_on):
    """With mode "on", matmul_any routes tileable int4 einsums through the
    kernel (interpreted on the CPU backend) and matches the XLA path."""
    rs = np.random.RandomState(1)
    w, qt = _q4(rs, 256, 256)
    x3 = jnp.asarray(rs.randn(2, 3, 256).astype("float32"))
    assert kernel_wants("btd,df->btf", x3, qt)
    got = quant.matmul_any("btd,df->btf", x3, qt)
    set_kernel_mode("off")
    ref = quant.matmul_any("btd,df->btf", x3, qt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_wants_rejects_unsupported(kernel_on):
    rs = np.random.RandomState(2)
    _, qt = _q4(rs, 256, 256)
    x = jnp.asarray(rs.randn(4, 256).astype("float32"))
    assert kernel_wants("bd,df->bf", x, qt)
    # untileable N
    _, qt_small = _q4(rs, 256, 96)
    assert not kernel_wants("bd,df->bf", x, qt_small)
    # stacked [L, K/2, N] payload (inside scan slicing it becomes 2-D)
    wL = jnp.asarray(rs.randn(2, 256, 256).astype("float32") * 0.05)
    qtL = quant.quantize_weight(wL, (1,), bits=4)
    assert not kernel_wants("bd,ldf->lbf", x, qtL)
    # contraction not on x's last axis
    assert not kernel_wants("db,df->bf", x, qt)
    set_kernel_mode("off")
    assert not kernel_wants("bd,df->bf", x, qt)


def test_int4_engine_tokens_unchanged_by_kernel_path(kernel_on):
    """A tileable-width spec decodes the same greedy tokens through the
    kernel path (interpret) and the XLA path — guards the engine-level
    wiring, not just the op."""
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.engine import Engine
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )
    from distributed_inference_engine_tpu.models.llama import llama_spec
    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    spec = llama_spec("llama-tiny", max_seq_len=64).replace(
        d_model=256, d_ff=256, n_heads=4, n_kv_heads=4, dtype="float32")
    params = random_quantized_params(spec, jax.random.key(0), bits=4)
    cfg = EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=[16],
                       decode_steps_per_call=4)
    reqs = lambda: [GenerationRequest(prompt=[1, 2, 3, 4], max_new_tokens=6,
                                      temperature=0.0, request_id="k")]
    t_kernel = Engine(spec, params=params, config=cfg).generate(reqs())[0]
    set_kernel_mode("off")
    t_xla = Engine(spec, params=params, config=cfg).generate(reqs())[0]
    assert t_kernel.tokens == t_xla.tokens


def test_stacked_kernel_layer_indexed_matches_sliced(kernel_on):
    """The scalar-prefetch stacked kernel (layer picked by the grid's
    index_map, no materialized slice) must match the per-layer 2-D
    kernel for every layer, and matmul_any must route IndexedQuant to
    it."""
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        int4_einsum_kernel_stacked,
        stacked_kernel_wants,
    )

    rs = np.random.RandomState(7)
    L, K, N = 3, 256, 384
    w = jnp.asarray(rs.randn(L, K, N).astype("float32") * 0.05)
    qt = quant.quantize_weight(w, (1,), bits=4)
    assert stacked_kernel_wants(qt)
    x = jnp.asarray(rs.randn(4, K).astype("float32"))
    for l in range(L):
        per_layer = quant.QuantizedTensor(q=qt.q[l], s=qt.s[l],
                                          bits=4, pack_axis=qt.pack_axis)
        ref = quant.matmul_any("bd,df->bf", x, per_layer)
        got = int4_einsum_kernel_stacked("bd,df->bf", x, qt, jnp.int32(l))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        via_any = quant.matmul_any("bd,df->bf", x,
                                   quant.IndexedQuant(qt, jnp.int32(l)))
        np.testing.assert_array_equal(np.asarray(via_any), np.asarray(got))


def test_split_indexed_blocks_identity_when_off():
    """With the kernel disabled the split is an identity — the XLA paths
    keep their scanned-slice fusion."""
    from distributed_inference_engine_tpu.ops.quant import (
        split_indexed_blocks,
    )

    set_kernel_mode("off")
    try:
        rs = np.random.RandomState(3)
        w = jnp.asarray(rs.randn(2, 64, 64).astype("float32"))
        blocks = {"wq": quant.quantize_weight(w, (1,), bits=4),
                  "ln1_scale": jnp.ones((2, 64))}
        xs, rebuild = split_indexed_blocks(blocks)
        assert set(xs) == {"wq", "ln1_scale"}
        blk = rebuild({k: jax.tree.map(lambda a: a[0], v)
                       for k, v in xs.items()}, 0)
        assert not isinstance(blk["wq"], quant.IndexedQuant)
    finally:
        set_kernel_mode("auto")
