"""int4 Mosaic kernel x tensor parallelism (r5, VERDICT r4 item 4).

The stacked kernel was single-device-only through r4 — a pallas_call is
opaque to GSPMD, so tp-sharded int4 payloads fell back to the XLA path
(the measured 1,584 vs 4,254 tok/s loss). Mode "cp" wraps the kernel in
a ``custom_partitioning`` op with a Shardy rule: x rides pre-split as
(xlo, xhi) so both halves' K/2 axis and the payload's packed axis share
one reduction factor — the split-half layout shards COHERENTLY for
row-parallel weights (no repacking) and trivially for column-parallel.

These tests run the cp path on the virtual 8-device CPU mesh (kernel
interpreted), exactly how the driver's dryrun validates multi-chip
shardings without hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.config import EngineConfig, MeshConfig
from distributed_inference_engine_tpu.engine.engine import Engine
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models.llama import llama_spec
from distributed_inference_engine_tpu.ops import quant
from distributed_inference_engine_tpu.ops.int4_matmul import (
    kernel_mode,
    set_kernel_mode,
)
from distributed_inference_engine_tpu.parallel.mesh import make_mesh
from distributed_inference_engine_tpu.parallel.sharding import ModelShardings


@pytest.fixture(autouse=True)
def reset_mode():
    """The auto "cp" selection is per-tensor now (resolve_kernel_modes
    stamps the engine's own params), but the module default is still
    settable explicitly / via env; keep tests hermetic."""
    yield
    set_kernel_mode("auto")


# dims chosen so the LOCAL tp=2 shards still tile the kernel's block
# candidates (>=128): wq N=512/2=256, w_down k2=256/2=128
def _spec():
    return llama_spec("llama-tiny", max_seq_len=64).replace(
        d_model=512, d_ff=512, n_heads=4, n_kv_heads=2, vocab_size=1024,
        dtype="float32")


def test_cp_matmul_column_and_row_sharded_match_reference():
    """The custom_partitioning op partitions both tp layouts without
    gathering: column (N-sharded) and row (packed-axis-sharded, psum)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_inference_engine_tpu.ops.int4_matmul import _cp_stacked

    L, K, N = 2, 2048, 1024
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(L, K, N).astype("float32") * 0.05)
    qt = quant.quantize_weight(w, (1,), bits=4)
    x = jnp.asarray(rs.randn(16, K).astype("float32"))
    k2 = K // 2
    xlo, xhi = x[:, :k2], x[:, k2:]
    s32 = qt.s.astype(jnp.float32)
    ref = jnp.einsum("md,df->mf", x, qt.dequantize(jnp.float32)[1])
    mesh = make_mesh(MeshConfig(tp=8))
    cp = _cp_stacked(True)

    @jax.jit
    def run(xlo, xhi, q, s):
        return cp(xlo, xhi, q, s, jnp.int32([1]))

    col = run(jax.device_put(xlo, NamedSharding(mesh, P())),
              jax.device_put(xhi, NamedSharding(mesh, P())),
              jax.device_put(qt.q, NamedSharding(mesh, P(None, None, "tp"))),
              jax.device_put(s32, NamedSharding(mesh, P(None, None, "tp"))))
    np.testing.assert_allclose(np.asarray(col), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    row = run(jax.device_put(xlo, NamedSharding(mesh, P(None, "tp"))),
              jax.device_put(xhi, NamedSharding(mesh, P(None, "tp"))),
              jax.device_put(qt.q, NamedSharding(mesh, P(None, "tp", None))),
              jax.device_put(s32, NamedSharding(mesh, P())))
    np.testing.assert_allclose(np.asarray(row), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_tp_int4_engine_matches_xla_path():
    """End-to-end: a tp=2 Engine over int4 params auto-selects mode "cp"
    (stamped on ITS OWN tensors — the kernel partitions instead of
    gathering) and decodes the same greedy tokens as the unsharded XLA
    int4 path."""
    spec = _spec()
    params = quant.random_quantized_params(spec, jax.random.key(0), bits=4)
    cfg = EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=[16],
                       kv_dtype="float32", decode_steps_per_call=4)
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, spec.vocab_size, size=9).tolist()
               for _ in range(2)]

    def reqs():
        return [GenerationRequest(prompt=list(p), max_new_tokens=6,
                                  temperature=0.0, request_id=f"t{i}")
                for i, p in enumerate(prompts)]

    base = Engine(spec, params=params, config=cfg, seed=0)
    out_base = base.generate(reqs())          # traces on the XLA path
    assert kernel_mode() == "auto"

    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), jax.devices()[:2])
    shardings = ModelShardings.build(spec, mesh)
    with mesh:
        tp = Engine(spec, params=params, config=cfg, seed=0,
                    shard_fn=shardings.shard_fn())
        assert kernel_mode() == "auto"        # process state untouched
        wq = tp.params["blocks"]["wq"]
        assert wq.kernel_mode == "cp"         # stamped by param placement
        assert len(wq.q.sharding.device_set) == 2
        out_tp = tp.generate(reqs())
    for a, b in zip(out_base, out_tp):
        assert a.tokens == b.tokens, (a.tokens, b.tokens)


def test_tp_int4_untileable_local_falls_back_not_fails():
    """A spec whose LOCAL shards don't tile the kernel blocks must still
    produce correct tokens via the cp op's local XLA fallback."""
    spec = llama_spec("llama-tiny", max_seq_len=64).replace(
        d_model=256, d_ff=256, n_heads=4, n_kv_heads=2, vocab_size=512,
        dtype="float32")
    params = quant.random_quantized_params(spec, jax.random.key(1), bits=4)
    cfg = EngineConfig(max_slots=1, max_seq_len=64, prefill_buckets=[16],
                       kv_dtype="float32", decode_steps_per_call=4)
    req = [GenerationRequest(prompt=[3, 5, 7, 9], max_new_tokens=5,
                             temperature=0.0, request_id="f")]
    base = Engine(spec, params=params, config=cfg, seed=0)
    out_base = base.generate(req)
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), jax.devices()[:2])
    shardings = ModelShardings.build(spec, mesh)
    with mesh:
        tp = Engine(spec, params=params, config=cfg, seed=0,
                    shard_fn=shardings.shard_fn())
        assert tp.params["blocks"]["wq"].kernel_mode == "cp"
        out_tp = tp.generate(req)
    assert out_base[0].tokens == out_tp[0].tokens


def test_two_engines_different_meshes_do_not_cross_contaminate():
    """A tp engine's "cp" selection must not leak into a single-device
    engine built afterwards in the same process (the old implementation
    flipped module state as an Engine-construction side effect, so the
    SECOND engine inherited the first one's kernel mode — its decode
    then dispatched the multi-device cp wrapper on replicated params)."""
    spec = _spec()
    params = quant.random_quantized_params(spec, jax.random.key(2), bits=4)
    cfg = EngineConfig(max_slots=1, max_seq_len=64, prefill_buckets=[16],
                       kv_dtype="float32", decode_steps_per_call=4)
    req = [GenerationRequest(prompt=[2, 4, 6, 8, 10], max_new_tokens=5,
                             temperature=0.0, request_id="x")]

    # reference tokens from a clean process state
    out_ref = Engine(spec, params=params, config=cfg, seed=0).generate(req)

    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), jax.devices()[:2])
    shardings = ModelShardings.build(spec, mesh)
    with mesh:
        tp = Engine(spec, params=params, config=cfg, seed=0,
                    shard_fn=shardings.shard_fn())
    assert tp.params["blocks"]["wq"].kernel_mode == "cp"

    # second engine, unsharded: its tensors stay unstamped, the process
    # default is still "auto", and its decode takes the single-device
    # path — under the old global flip this generate() dispatched cp
    solo = Engine(spec, params=params, config=cfg, seed=0)
    assert kernel_mode() == "auto"
    modes = {
        leaf.kernel_mode
        for leaf in jax.tree.leaves(
            solo.params, is_leaf=lambda x: isinstance(x, quant.QuantizedTensor))
        if isinstance(leaf, quant.QuantizedTensor)
    }
    assert modes == {""}, modes
    assert solo.generate(req)[0].tokens == out_ref[0].tokens


def test_kernel_eligibility_follows_the_weight_not_the_device_count(
        monkeypatch):
    """Eight devices are visible. On an accelerator backend a
    single-device int4 weight is kernel-eligible all the same (through r20
    "auto" needed ``len(jax.devices()) == 1``, so a tp=1 deploy on a
    multi-chip host silently took the XLA path), it takes the DIRECT call,
    and the same tree placed on a tp mesh resolves to "cp"."""
    from distributed_inference_engine_tpu.ops.int4_matmul import (
        kernel_path,
        stacked_kernel_wants,
    )
    from distributed_inference_engine_tpu.parallel.sharding import (
        shard_params,
    )

    assert len(jax.devices()) == 8
    spec = _spec()
    params = quant.random_quantized_params(spec, jax.random.key(3), bits=4)
    wq = params["blocks"]["wq"]
    assert len(wq.q.sharding.device_set) == 1
    # this suite's backend is cpu: "auto" keeps the XLA path there
    assert not stacked_kernel_wants(wq) and kernel_path(wq) == "xla"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert stacked_kernel_wants(wq) and kernel_path(wq) == "direct"
    solo = quant.resolve_kernel_modes(params)
    assert solo["blocks"]["wq"].kernel_mode == ""
    paths = quant.int4_kernel_paths(quant.prepare_params(params))
    assert paths["xla"] == 0 and paths["cp"] == 0 and paths["direct"] > 0

    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=2), jax.devices()[:2])
    sharded = quant.prepare_params(
        shard_params(params, ModelShardings.build(spec, mesh)))
    assert sharded["blocks"]["wq"].kernel_mode == "cp"
    paths = quant.int4_kernel_paths(sharded)
    assert paths["xla"] == 0 and paths["direct"] == 0 and paths["cp"] > 0
