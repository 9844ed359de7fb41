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


# rows 1-16 take the decode bucket (the streaming kernel: chunks of
# ``[bk, bn]`` by its own DMAs, two ahead), 17+ the prefill bucket (the
# pipelined grid); explicit blocks cover one chunk a step, several chunks
# a step, fewer chunks than the DMAs run ahead, and a k axis of one
@pytest.mark.parametrize("m,k,n,blocks", [
    (5, 256, 256, {}), (64, 512, 384, {}), (16, 256, 128, {}),
    (1, 512, 256, {}), (3, 512, 256, {}), (8, 512, 256, {}),
    (16, 512, 256, {}), (128, 512, 256, {}), (160, 512, 384, {}),
    (1, 1024, 256, {"bk": 512, "bn": 128}),     # full-K chunks, 2 steps
    (3, 1024, 256, {"bk": 512, "bn": 256}),     # ONE chunk in all
    (8, 1024, 512, {"bk": 256, "bn": 512}),     # 2 chunks, one step
    (16, 1024, 512, {"bk": 128, "bn": 256}),    # 4 chunks a step, 2 steps
    (8, 1024, 384, {"bk": 128, "bn": 128}),     # ... 3 steps
    (9, 512, 256, {"bk": 256, "bn": 256}),
    (128, 1024, 256, {"bk": 512, "bn": 256}),   # grid, k axis of one
    (384, 1024, 256, {"bk": 256, "bn": 128}),   # grid, 3 row tiles
])
def test_kernel_matches_dequantized_reference(m, k, n, blocks):
    rs = np.random.RandomState(m + k + n)
    w, qt = _q4(rs, k, n)
    x = jnp.asarray(rs.randn(m, k).astype("float32"))
    ref = jnp.einsum("md,df->mf", x, qt.dequantize(jnp.float32))
    got = _int4_matmul_2d(x, qt.q, qt.s.astype(jnp.float32), interpret=True,
                          **blocks)
    assert got.shape == (m, n)
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


@pytest.mark.parametrize("m", [4, 1, 8, 16, 136])
def test_stacked_kernel_layer_indexed_matches_sliced(kernel_on, m):
    """The scalar-prefetch stacked kernel (layer picked by the grid's
    index_map, no materialized slice) must match the per-layer 2-D
    kernel for every layer, at decode rows and at prefill rows, and
    matmul_any must route IndexedQuant to it."""
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        int4_einsum_kernel_stacked,
        stacked_kernel_wants,
    )

    rs = np.random.RandomState(7)
    L, K, N = 3, 256, 384
    w = jnp.asarray(rs.randn(L, K, N).astype("float32") * 0.05)
    qt = quant.quantize_weight(w, (1,), bits=4)
    assert stacked_kernel_wants(qt)
    x = jnp.asarray(rs.randn(m, K).astype("float32"))
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


# the five payload shapes of mistral-7b as the engine fuses them
MISTRAL_SHAPES = [(2048, 6144), (2048, 4096), (2048, 28672), (7168, 4096),
                  (2048, 32768)]


def test_every_table_entry_divides_its_shape():
    from distributed_inference_engine_tpu.ops import int4_matmul as im

    for (k2, n), buckets in im._TUNED_BLOCKS.items():
        assert len(buckets) == 2
        for bk, bn in filter(None, buckets):    # None: bucket not swept
            assert k2 % bk == 0 and n % bn == 0, (k2, n, bk, bn)
            # what Mosaic tiles: lanes of the activation and output blocks,
            # sublanes of the int8 payload block
            assert bn % 128 == 0 and (bk % 128 == 0 or bk == k2)


def _launched_blocks(rows, k2, n):
    """``(bk, bn)`` of the pallas_call ``_int4_matmul_stacked`` really
    builds for this call, read off its jaxpr (nothing runs): the shape of
    the int8 block the kernel body is handed — the grid's weight block at
    prefill rows, one slot of the DMA scratch at decode rows."""
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        _int4_matmul_stacked,
    )

    jaxpr = jax.make_jaxpr(
        lambda x, p, s: _int4_matmul_stacked(x, p, s, jnp.int32(0),
                                             interpret=True))(
        jax.ShapeDtypeStruct((rows, 2 * k2), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, k2, n), jnp.int8),
        jax.ShapeDtypeStruct((2, 1, n), jnp.float32))

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    calls = list(walk(jaxpr.jaxpr))
    assert len(calls) == 1, "one pallas_call a matmul (the trace counts them)"
    int8 = [v.aval.shape for v in calls[0].params["jaxpr"].invars
            if v.aval.dtype == jnp.int8 and v.aval.shape[-2:] != (k2, n)]
    assert len(int8) == 1, int8
    return int8[0][-2:], tuple(calls[0].params["grid_mapping"].grid)


@pytest.mark.parametrize("k2,n", MISTRAL_SHAPES)
@pytest.mark.parametrize("rows", [8, 256])
def test_mistral_shapes_resolve_to_a_table_entry(kernel_on, k2, n, rows):
    """Both row buckets of every shape the cells stream are measured table
    entries, and the report (``int4_blocks``) names the blocks the kernel's
    own grid is built from."""
    from distributed_inference_engine_tpu.ops import int4_matmul as im

    assert (k2, n) in im._TUNED_BLOCKS
    bk, bn = im.blocks_for(rows, k2, n)
    report = im.block_report(k2, n)
    assert report["tuned"]
    assert report["decode" if rows <= 16 else "prefill"] == [bk, bn]
    launched, grid = _launched_blocks(rows, k2, n)
    assert launched == (bk, bn)
    assert grid == ((n // bn,) if rows <= 16 else (rows // 128, n // bn,
                                                   k2 // bk))


def test_int4_kernel_blocks_reports_the_prepared_tree(kernel_on):
    """``ops.quant.int4_kernel_blocks`` lists each kernel-borne payload
    shape once, with ``tuned`` false where the default candidates serve
    it, and leaves out what ``kernel_path`` sends to XLA."""
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        block_report,
        blocks_for,
        kernel_path,
    )

    rs = np.random.RandomState(11)
    stacked = quant.quantize_weight(
        jnp.asarray(rs.randn(2, 256, 384).astype("float32")), (1,), bits=4)
    _, head = _q4(rs, 256, 512)
    _, odd = _q4(rs, 256, 96)                       # untileable N -> xla
    tree = {"blocks": {"w": stacked}, "lm_head": head, "odd": odd}
    assert [kernel_path(t) for t in (stacked, head, odd)] == \
        ["direct", "direct", "xla"]
    blocks = quant.int4_kernel_blocks(tree)
    assert set(blocks) == {"128x384", "128x512"}
    for key, (k2, n) in (("128x384", (128, 384)), ("128x512", (128, 512))):
        assert blocks[key] == {"decode": list(blocks_for(8, k2, n)),
                               "prefill": list(blocks_for(512, k2, n)),
                               "tuned": False}
    # a shape swept in one bucket only says so
    assert block_report(2048, 129024) == {
        "decode": [512, 2048], "prefill": [2048, 2048], "tuned": False}
    set_kernel_mode("off")
    assert quant.int4_kernel_blocks(tree) == {}


def test_worker_device_report_carries_int4_blocks(kernel_on):
    """The worker's per-model placement (``device.models.<name>`` of
    ``ping`` / ``metrics``) names the blocks beside the paths."""
    from types import SimpleNamespace

    from distributed_inference_engine_tpu.cluster.worker import (
        _engine_placement,
    )

    rs = np.random.RandomState(5)
    params = {"blocks": {"wo": quant.quantize_weight(
        jnp.asarray(rs.randn(2, 256, 256).astype("float32")), (1,), bits=4)}}
    place = _engine_placement(SimpleNamespace(params=params,
                                              attn_impl="xla"))
    assert place["int4_paths"] == {"direct": 1, "cp": 0, "xla": 0}
    assert place["int4_blocks"] == quant.int4_kernel_blocks(params) == {
        "128x256": {"decode": [128, 256], "prefill": [128, 256],
                    "tuned": False}}


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
