"""Fused decode megastep (ops/fused_decode.py + the models/base.py layer
seams): BIT-parity of norm_matmul / matmul_residual against the unfused
rms_norm + matmul chain, the eligibility gates (quantized carriers, bias
specs, non-tileable shapes fall back — never error), seam-level parity of
_qkv_norm / _out_residual / _mlp_residual, engine-level token parity of
decode_fused=True vs False (greedy and fixed-key sampled) across
f32/bf16/int8/int4 weights and bf16/fp8 KV pools, the compile-count
guard, the batched-firsts host cache, and device-side stop-id rows."""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.ops.fused_decode import (
    matmul_residual,
    matmul_residual_wants,
    norm_matmul,
    norm_matmul_wants,
)
from distributed_inference_engine_tpu.ops.norms import rms_norm

pytestmark = pytest.mark.kernels


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


# The bit references below are JITTED. Every engine call site runs the
# unfused chain inside a jitted decode program, and on jax 0.9.0 XLA CPU
# fuses rms_norm's f32 ``x * (1/sqrt(ms+eps)) * g`` differently under jit
# than op-by-op eager dispatch does (measured: eager vs jitted rms_norm
# differ by <=4 ulp on 29% of f32 elements, both equally far from an f64
# reference; bf16 rounds the difference away). The interpreted kernel is
# bit-identical to the jitted chain — the one that is actually served.
# The dot is spelled as the kernel must spell it for Mosaic (fp32
# accumulator, one rounding to the operand dtype — what the MXU does for a
# bf16 einsum); XLA's CPU backend rounds a plain bf16 dot differently, so
# that form is held to allclose below, not to bits.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _ref_norm_matmul(x, g, w, eps, pad):
    h = jnp.pad(rms_norm(x, g, eps), ((0, pad), (0, 0)))
    return jnp.dot(h, w, preferred_element_type=jnp.float32).astype(
        h.dtype)[:x.shape[0]]


# ------------------------------------------------------ kernel-level parity


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b", [1, 16, 37])
def test_norm_matmul_bit_parity(dtype, b):
    """Fused kernel == rms_norm-then-dot, BIT-exact (odd batches exercise
    the sublane padding path).

    The bit reference pins the contraction at the kernel's padded batch
    (B rounded up to 16 sublanes, sliced back) because XLA CPU under
    conftest's --xla_force_host_platform_device_count=8 picks a different
    f32 accumulation blocking for M<16 vs M=16 at N>=512 — last-bit
    mantissa only.  The TPU MXU always runs the padded tile, and the
    engine-level parity tests below cover the served-token contract; the
    unpadded form is held to allclose here to catch real kernel bugs."""
    d, n = 256, 512
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (b, d), jnp.float32).astype(dtype)
    g = (1.0 + 0.1 * jax.random.normal(ks[1], (d,), jnp.float32)).astype(dtype)
    w = jax.random.normal(ks[2], (d, n), jnp.float32).astype(dtype)
    assert norm_matmul_wants(x, w)
    ref = _ref_norm_matmul(x, g, w, 1e-5, (-b) % 16)
    got = norm_matmul(x, g, w, eps=1e-5, interpret=True)
    _bits_equal(got, ref)
    # vs the plain eager dot: fp32 to rounding noise, bf16 to one ulp
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(jnp.dot(rms_norm(x, g, 1e-5), w), np.float32),
        rtol=tol, atol=1e-4)


def test_norm_matmul_plus_one_gemma():
    """norm_plus_one: the (w - 1) storage convention adds the 1 back in
    fp32 inside the kernel — same bits as _norm's pre-add."""
    d, n = 128, 256
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (4, d), jnp.float32)
    g = 0.1 * jax.random.normal(ks[1], (d,), jnp.float32)
    w = jax.random.normal(ks[2], (d, n), jnp.float32)
    ref = _ref_norm_matmul(x, g.astype(jnp.float32) + 1.0, w, 1e-6, 0)
    got = norm_matmul(x, g, w, eps=1e-6, plus_one=True, interpret=True)
    _bits_equal(got, ref)


@pytest.mark.parametrize("dtype,b", [(jnp.float32, 3), (jnp.bfloat16, 16)])
def test_matmul_residual_bit_parity(dtype, b):
    d, n = 256, 128
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (b, d), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (d, n), jnp.float32).astype(dtype)
    res = jax.random.normal(ks[2], (b, n), jnp.float32).astype(dtype)
    assert matmul_residual_wants(x, w)
    ref = res + jnp.dot(x, w)
    got = matmul_residual(x, w, res, interpret=True)
    _bits_equal(got, ref)


def test_kernels_under_jit():
    """The engine call sites are jitted — the kernels must trace."""
    d, n = 128, 128
    ks = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(ks[0], (2, d), jnp.float32)
    g = jnp.ones((d,), jnp.float32)
    w = jax.random.normal(ks[1], (d, n), jnp.float32)
    res = jax.random.normal(ks[2], (2, n), jnp.float32)
    got = jax.jit(lambda *a: norm_matmul(*a, interpret=True))(x, g, w)
    _bits_equal(got, _ref_norm_matmul(x, g, w, 1e-6, 0))
    got = jax.jit(lambda *a: matmul_residual(*a, interpret=True))(x, w, res)
    _bits_equal(got, res + jnp.dot(x, w))


# ---------------------------------------------------------- eligibility gates


def test_wants_gates():
    x = jnp.zeros((4, 256), jnp.float32)
    w = jnp.zeros((256, 512), jnp.float32)
    assert norm_matmul_wants(x, w)
    assert matmul_residual_wants(x, w)
    # quantized carriers (QuantizedTensor has .q, IndexedQuant has .qt)
    # must keep riding matmul_any's kernel dispatch
    assert not norm_matmul_wants(x, SimpleNamespace(q=object(), ndim=2))
    assert not norm_matmul_wants(x, SimpleNamespace(qt=object(), ndim=2))
    # dtype mismatch between activation and weight
    assert not norm_matmul_wants(x.astype(jnp.bfloat16), w)
    # non-lane-tileable dims fall back, never error
    assert not norm_matmul_wants(x, jnp.zeros((256, 200), jnp.float32))
    assert not norm_matmul_wants(
        jnp.zeros((4, 200), jnp.float32), jnp.zeros((200, 512), jnp.float32))
    # rank gates: 3-D activations / 3-D (stacked) weights
    assert not norm_matmul_wants(x[None], w)
    assert not norm_matmul_wants(x, jnp.zeros((2, 256, 512), jnp.float32))


# ---------------------------------------------------------- model-layer seams


def _tiny_spec(dtype="float32"):
    from distributed_inference_engine_tpu.models.base import ModelSpec

    return ModelSpec(
        vocab_size=256, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=128, dtype=dtype,
    )


def test_layer_seam_parity():
    """The three megastep seams (_qkv_norm, _out_residual, _mlp_residual)
    produce BIT-identical outputs fused vs unfused on an eligible layer —
    the per-layer guarantee the engine-level token parity rests on."""
    from distributed_inference_engine_tpu.models import base as mbase

    spec = _tiny_spec()
    params = mbase.init_params(spec, jax.random.key(0))
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    ks = jax.random.split(jax.random.key(4), 2)
    x = jax.random.normal(ks[0], (3, 1, spec.d_model), jnp.float32)
    positions = jnp.asarray([[5], [9], [63]], jnp.int32)
    # preconditions: the tiny spec really is kernel-eligible
    assert norm_matmul_wants(x.reshape(3, spec.d_model), blk["wq"])

    def seam(fn, fused):
        # jitted on both sides, as in the decode program (see the note
        # above _ref_norm_matmul)
        return jax.jit(lambda blk, *a: fn(spec, blk, *a, fused=fused))

    q0, k0, v0 = seam(mbase._qkv_norm, False)(blk, x, positions)
    q1, k1, v1 = seam(mbase._qkv_norm, True)(blk, x, positions)
    _bits_equal(q1, q0)
    _bits_equal(k1, k0)
    _bits_equal(v1, v0)

    attn = jax.random.normal(ks[1], (3, 1, spec.n_heads, spec.head_dim),
                             jnp.float32)
    _bits_equal(seam(mbase._out_residual, True)(blk, attn, x),
                seam(mbase._out_residual, False)(blk, attn, x))

    m0, a0 = seam(mbase._mlp_residual, False)(blk, x)
    m1, a1 = seam(mbase._mlp_residual, True)(blk, x)
    _bits_equal(m1, m0)
    assert float(a0) == float(a1) == 0.0


def test_layer_seam_fallbacks():
    """Ineligible specs (layernorm, biases, quantized carriers) take the
    unfused chain under fused=True — same values, no error."""
    from distributed_inference_engine_tpu.models import base as mbase

    spec = _tiny_spec().replace(norm="layernorm")
    params = mbase.init_params(spec, jax.random.key(1))
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.key(5), (2, 1, spec.d_model),
                          jnp.float32)
    positions = jnp.asarray([[3], [7]], jnp.int32)
    q0, k0, v0 = mbase._qkv_norm(spec, blk, x, positions, fused=False)
    q1, k1, v1 = mbase._qkv_norm(spec, blk, x, positions, fused=True)
    _bits_equal(q1, q0)
    _bits_equal(k1, k0)
    _bits_equal(v1, v0)


# ------------------------------------------------------------- engine level


def _mk_pair(spec=None, params=None, extra=None):
    """Two continuous engines sharing one param tree: decode_fused off/on."""
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )

    spec = spec or _tiny_spec()
    base = dict(max_slots=2, max_seq_len=64, prefill_buckets=[16],
                page_size=16, num_pages=16, decode_steps_per_call=4)
    base.update(extra or {})
    ref = ContinuousEngine(spec, params=params, config=EngineConfig(
        decode_fused=False, **base), seed=0)
    fz = ContinuousEngine(spec, params=ref.params, config=EngineConfig(
        decode_fused=True, **base), seed=0)
    return ref, fz


def _reqs(temperature=0.0, n=3, new=8):
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )

    return [GenerationRequest(
        prompt=[(5 * i + j) % 250 + 1 for j in range(4 + 3 * i)],
        max_new_tokens=new, temperature=temperature,
        top_p=0.9 if temperature else 1.0,
        request_id=f"r{i}") for i in range(n)]


def _run_pair(ref, fz):
    """Both engines over a greedy wave then a fixed-key sampled wave;
    token dicts must match exactly (bit-equivalent logits + the same
    per-engine rng stream => the same sampled draws)."""
    for temp in (0.0, 0.7):
        a = {r.request_id: r.tokens for r in ref.generate(_reqs(temp))}
        b = {r.request_id: r.tokens for r in fz.generate(_reqs(temp))}
        assert a == b, f"token mismatch at temperature={temp}"
        assert all(v for v in a.values())


@pytest.mark.slow
@pytest.mark.parametrize("wdtype", ["float32", "bfloat16"])
def test_engine_token_parity_plain(wdtype):
    """decode_fused=True is token-for-token identical (greedy AND sampled
    with the engine's seeded key stream) on plain weight trees — the
    configs where the Pallas kernels actually engage."""
    ref, fz = _mk_pair(spec=_tiny_spec(wdtype))
    _run_pair(ref, fz)


@pytest.mark.slow
@pytest.mark.parametrize("bits", [8, 4])
def test_engine_token_parity_quantized(bits):
    """Quantized trees (int8 / packed int4) must NOT route to the fused
    kernels (dequant already rides the matmul; scales live on N) — the
    flag is a no-op there and tokens stay identical."""
    from distributed_inference_engine_tpu.ops.quant import (
        random_quantized_params,
    )

    spec = _tiny_spec()
    params = random_quantized_params(spec, jax.random.key(0), bits=bits)
    ref, fz = _mk_pair(spec=spec, params=params)
    _run_pair(ref, fz)


@pytest.mark.slow
def test_engine_token_parity_fp8_kv():
    """bf16 weights + fp8 KV pool: the KV cast happens outside the fused
    seams, so parity must hold bit-for-bit."""
    ref, fz = _mk_pair(spec=_tiny_spec("bfloat16"),
                       extra=dict(kv_dtype="float8_e4m3fn"))
    _run_pair(ref, fz)


def _programs(eng):
    """Program-shape keys of the dispatches in the engine's step ring."""
    return {e["args"]["program"] for e in eng.timeline.events()
            if "program" in e["args"]}


@pytest.mark.slow
def test_engine_compile_count_guard():
    """Fusion must not multiply jit buckets: the fused engine's dispatched
    program-shape set is identical to the unfused engine's, and a second
    wave compiles nothing new."""
    ref, fz = _mk_pair()
    ref.generate(_reqs())
    fz.generate(_reqs())
    progs1 = _programs(fz)
    fz.generate(_reqs())
    assert _programs(fz) == progs1                 # no growth across waves
    assert _programs(fz) == _programs(ref)
    assert any(p[0] == "decode" for p in progs1)


# ------------------------------------------- batched firsts readback (cache)


@pytest.fixture(scope="module")
def plain_engine():
    """ONE unfused engine shared by the host-path tests below — each
    leaves all slots drained, and sharing skips re-jitting the whole
    program set per test (tier-1 runs against a hard wall clock)."""
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )

    return ContinuousEngine(_tiny_spec(), config=EngineConfig(
        max_slots=2, max_seq_len=64, prefill_buckets=[16], page_size=16,
        num_pages=16, decode_steps_per_call=4, decode_fused=False), seed=0)


def test_firsts_snapshot_cache(plain_engine):
    """The packed chunk output carries the whole firsts buffer, so sync
    processing caches it host-side for free; rescue reads go through
    _firsts_snapshot() — one whole-buffer transfer at most, and the cache
    invalidates when an admission rewrites the device columns."""
    eng = plain_engine
    assert eng._firsts_host is None
    res = eng.generate(_reqs(n=2))
    assert all(r.tokens for r in res)
    # a sync decode chunk ran -> the packed read populated the cache
    assert eng._firsts_host is not None
    np.testing.assert_array_equal(eng._firsts_snapshot(),
                                  np.asarray(eng._firsts_dev))
    # stale-path: drop the cache, the snapshot refetches the device buffer
    eng._firsts_host = None
    snap = eng._firsts_snapshot()
    np.testing.assert_array_equal(snap, np.asarray(eng._firsts_dev))
    assert eng._firsts_host is not None
    # a second wave re-admits (install rewrites firsts columns -> cache
    # invalidated mid-run) and must still finish with a consistent cache
    eng.generate(_reqs(n=2))
    np.testing.assert_array_equal(eng._firsts_snapshot(),
                                  np.asarray(eng._firsts_dev))


# ------------------------------------------------------- device-side stop ids


def test_device_stop_ids(plain_engine):
    """stop_ids ride to the device as a [slots, K] matrix: the slot's row
    holds the ids (-1 padded), the decode loop exits at a hit, and the
    host trimmer keeps the matched stop (same contract as eos)."""
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )

    eng = plain_engine
    base = dict(prompt=[7, 11, 13], max_new_tokens=12, temperature=0.0)
    free = eng.generate([GenerationRequest(request_id="free", **base)])[0]
    assert len(free.tokens) == 12
    stop_tok = free.tokens[2]
    cut = free.tokens.index(stop_tok) + 1          # earliest hit, inclusive

    req = GenerationRequest(request_id="stopped", stop_ids=[stop_tok],
                            **base)
    eng.submit(req)
    eng.step()                                     # admission installs
    rows = np.asarray(eng._stops_dev)
    assert (rows == stop_tok).any(), "stop id never reached the device"
    while eng.n_live or eng.n_waiting:
        eng.step()
    res = eng.drain_finished()[0]
    assert res.finish_reason == "stop"
    assert res.tokens == free.tokens[:cut]
    # the freed slot's row resets so a stale id cannot stop the next tenant
    done = eng.generate([GenerationRequest(request_id="after", **base)])[0]
    assert done.tokens == free.tokens
