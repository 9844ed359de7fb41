"""Kernels of the served path compiled for the chip, without the chip: the
TPU compiler is installed here and compiles for a DESCRIBED v5e, so what
Mosaic would refuse on the machine (a slice off the tiling, too much VMEM,
an op with no lowering) fails here at no chip time. Nothing runs: a compile
that passes says nothing about results or times.

The topology is described inside a fixture, never at import (only one
process may load the TPU library; see the on-chip-measurement guide), and
every such test lives in this one file.
"""

import re

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


# B, pages a row, side window, pages a block and pages a softmax update
# (0: the tuned defaults)
@pytest.mark.parametrize("b,mp,w,bp,ap", [
    (8, 68, 16, 0, 0),    # the Xing cell: 8 slots of 8,704 positions
    (8, 24, 16, 0, 0),    # the Ling cell's one MLA layer
    (8, 68, 1, 0, 0),     # a chunk cut to one step
    (8, 68, 5, 0, 0),     # ... at max_seq_len's edge
    (8, 68, 16, 4, 1), (8, 68, 16, 4, 2), (8, 68, 16, 8, 4),
    (8, 68, 16, 8, 8), (8, 68, 16, 16, 8), (8, 68, 16, 16, 16),
])
def test_latent_decode_compiles_for_v5e(one_chip, b, mp, w, bp, ap):
    """MLA's absorbed decode over the latent pool as both families hold it:
    32 heads against rows of 640 lanes (512 | 64 | 64 zero), 7 layers."""
    from distributed_inference_engine_tpu.ops.flash_decode import (
        latent_decode_attention_pallas)

    layers, n, p, h, lanes, rank = 7, b * mp, 128, 32, 640, 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pages, pt, plen, side, n_side, layer):
        return latent_decode_attention_pallas(
            q, pages, pt, plen, side, n_side, layer, v_lanes=rank,
            scale=0.1, n_pages_per_layer=n, pages_per_block=bp,
            pages_per_attend=ap)

    bf = jnp.bfloat16
    compiled = jax.jit(fn).lower(
        sds((b, h, lanes), bf), sds((layers * n, p, lanes), bf),
        sds((b, mp), jnp.int32), sds((b,), jnp.int32), sds((b, w, lanes), bf),
        sds((b,), jnp.int32), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "latent_decode_custom_call" in text
    # the pool is the kernel's operand where it lies: no copy of it
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


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


# the K|V-row families' prefill kernel alone: the sliding-window family's
# 32 : 4 heads of 128 at its smallest and largest buckets, full and with its
# window of 1,024 (16,896 = 33 blocks), the Gated-DeltaNet family's 30 MHA
# heads (6 heads a step), 7 query heads a K/V head, and two rows at once
@pytest.mark.parametrize("b,t,h,hkv,window", [
    (1, 512, 32, 4, 1024), (1, 16384, 32, 4, 0), (1, 16896, 32, 4, 1024),
    (1, 4096, 30, 30, 0), (1, 1024, 28, 4, 0), (2, 1024, 32, 4, 1024)])
def test_kv_prefill_compiles_for_v5e(one_chip, b, t, h, hkv, window):
    from distributed_inference_engine_tpu.ops import flash_prefill

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *a: flash_prefill.kv_prefill_attention(
        *a, hkv, window=window, impl="flash")).lower(
            sds((b, t, h, 128)), sds((b, t, 2 * hkv * 128)),
            sds((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "kv_prefill_flash" in text
    # no score tensor in HBM: nothing float32 of 512 query rows a head
    assert not re.search(r"f32\[[0-9,]*\b512,[0-9]+\]", text)


# the in-place decode kernel over ONE pool of K|V rows (``kv_fused``: each
# half of a page's lanes copied where it lies), at the Gated-DeltaNet
# family's served shape (30 K/V heads of 128, 48 pages a row, chunks of 16)
# and at its chunk cut to one step
@pytest.mark.parametrize("w", [16, 1])
def test_flash_decode_over_a_fused_kv_pool_compiles_for_v5e(one_chip, w):
    from distributed_inference_engine_tpu.ops.flash_decode import (
        flash_decode_attention_pallas)

    b, h, dh, mp, layers, n, p = 8, 30, 128, 48, 4, 384, 128

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, pt, plen, sk, sv, n_side, layer):
        return flash_decode_attention_pallas(
            q, pool, pool, pt, plen, sk, sv, n_side, n_kv_heads=h,
            layer=layer, n_pages_per_layer=n, kv_fused=True)

    compiled = jax.jit(fn).lower(
        sds((b, h, dh)), sds((layers * n, p, 2 * h * dh)),
        sds((b, mp), jnp.int32), sds((b,), jnp.int32), sds((b, w, h, dh)),
        sds((b, w, h, dh)), sds((b,), jnp.int32),
        sds((), jnp.int32)).compile()
    assert "flash_decode_custom_call" in compiled.as_text()


# layers, heads, dk, dv, the gate's last axis, periods of the scan (0: the
# layers unrolled under static indices)
@pytest.mark.parametrize("nl,h,dk,dv,dg,periods", [
    (12, 30, 96, 192, 1, 4),       # the Gated-DeltaNet cell: (p, j) traced
    (6, 32, 128, 128, 128, 0),     # the KDA cell
])
def test_kda_step_inplace_compiles_for_v5e(one_chip, nl, h, dk, dv, dg,
                                           periods):
    """The delta rule's in-place decode step at both cells' state shapes (8
    slots; the 96 x 192 states two a row of 384 lanes, as the engine keeps
    them): Mosaic takes whole rows of states in a block, each head's column
    spread over its own 192 lanes, a layer index traced inside a
    ``lax.scan``, and the state array aliased to the output: the program
    holds no second copy of it."""
    from distributed_inference_engine_tpu.ops import kda

    b, m = 8, kda.lane_pack(h, dv)

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(S_all, q, k, v, g, beta, active):
        def layer(S_all, at):
            o, S_all = kda.kda_step_inplace(S_all, at, q, k, v, g, beta,
                                            active, impl="inplace")
            return S_all, o

        if not periods:
            outs = []
            for at in range(nl):
                S_all, o = layer(S_all, at)
                outs.append(o)
            return S_all, jnp.stack(outs)

        def period(S_all, p):
            outs = []
            for j in range(nl // periods):
                S_all, o = layer(S_all, p * (nl // periods) + j)
                outs.append(o)
            return S_all, jnp.stack(outs)

        return jax.lax.scan(period, S_all, jnp.arange(periods))

    compiled = jax.jit(fn, donate_argnums=0).lower(
        sds(nl, b, h // m, dk, m * dv), sds(b, h, dk), sds(b, h, dk),
        sds(b, h, dv),
        sds(b, h, dg), sds(b, h), sds(b, dtype=jnp.bool_)).compile()
    assert "kda_step_inplace" in compiled.as_text()
    one_layer = b * h * dk * dv * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer


def test_no_program_of_the_gdn_family_holds_a_copy_of_its_kv_table(
        one_chip, monkeypatch):
    """The Gated-DeltaNet family's decode chunk and its 4,096-token prefill
    at the served size, compiled for the v5e: the decode steps read the
    3.02 GB pool through the kernel where it lies, both programs write it
    where it lies, and neither holds a gathered ``[L, B, S, 7680]`` (or one
    layer's ``[B, S, 7680]``) context, nor temporaries as large as the pool
    (a copy of it would add 3.02 GB to the prefill's 1.5 GB of activations).
    Nor, since PR 34, anything of the per-slot STATE's size but the step's
    kernel: the chunk's scan and the scan over periods carry the 12 x 8
    states of 15 x 96 x 384 to the kernel and back with no copy, slice,
    select or stack of them (``kda.step_impl`` is steered to the chip's
    answer here: this process sees the CPU)."""
    from distributed_inference_engine_tpu.models import olmo_hybrid as fam
    from distributed_inference_engine_tpu.models.base import unembed
    from distributed_inference_engine_tpu.ops import flash_prefill, kda

    monkeypatch.setattr(kda, "step_impl", lambda: "inplace")
    monkeypatch.setattr(flash_prefill, "prefill_impl",
                        lambda t, dh: "flash")

    spec = fam.olmo_hybrid_spec("olmo-hybrid-7b-pp2", max_seq_len=6144)
    slots, n_pages, page, mp, steps = 8, 384, 128, 48, 16

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_params(spec, jax.random.key(0))))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_state(spec, slots)))
    pool = arr(spec.paged_layers, n_pages, page, spec.cache_row_width,
               dtype=jnp.bfloat16)
    pool_bytes = 4 * n_pages * page * 7680 * 2

    def decode(params, pages, state, lengths, last, active, table):
        ctx = fam.decode_context(pages, table, "pallas-decode")
        side = jnp.zeros((spec.paged_layers, slots, steps,
                          spec.cache_row_width), pages.dtype)

        def step(carry, _):
            side, state, now, last = carry
            hidden, side, state, _m = fam.forward_decode_step(
                spec, params, last, now, lengths, ctx, side, state, active)
            tok = jnp.argmax(unembed(spec, params, hidden), -1)
            return (side, state, now + 1, tok.astype(jnp.int32)), tok

        (side, state, now, last), toks = jax.lax.scan(
            step, (side, state, lengths, last), None, length=steps)
        pages = fam.write_rows_into_pages(pages, side, table, now - lengths,
                                          lengths)
        return pages, state, toks

    def prefill(params, tokens, lens, pages, state, table, slot_ids):
        hidden, pages, state, _ = fam.forward_prefill_into_pages(
            spec, params, tokens, lens, pages, state, table, slot_ids)
        return hidden[:, -1], pages, state

    programs = (
        jax.jit(decode, donate_argnums=(1, 2)).lower(
            params, pool, state, arr(slots), arr(slots),
            arr(slots, dtype=jnp.bool_), arr(slots, mp)),
        jax.jit(prefill, donate_argnums=(3, 4)).lower(
            params, arr(1, 4096), arr(1), pool, state, arr(1, mp), arr(1)))
    for lowered in programs:
        compiled = lowered.compile()
        text = compiled.as_text()
        for shape in ("[4,8,6144,7680]", "[8,6144,7680]", "[8,48,128,7680]",
                      "[4,8,48,128,7680]"):
            assert shape not in text, shape
        assert compiled.memory_analysis().temp_size_in_bytes < 0.6 * pool_bytes
    # since PR 40 the prefill's full-attention layers run the flash kernel
    # (steered as above): no float32 ``[1, 30, 512, keys]`` scores, and the
    # temporaries under the XLA body's 1,529,178,624 B (1,517,031,936)
    pre = programs[1].compile()
    assert "kv_prefill_flash" in pre.as_text()
    assert not re.search(r"f32\[1,30,512,[0-9]+\]", pre.as_text())
    assert pre.memory_analysis().temp_size_in_bytes < 1_529_178_624
    text = programs[0].compile().as_text()
    assert "flash_decode_custom_call" in text
    state_ops = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z-]*)\(", line)
        if m and "8,15,96,384]" in m.group(1):
            state_ops.add(m.group(2))
    assert "custom-call" in state_ops and state_ops <= {
        "parameter", "tuple", "get-tuple-element", "while", "bitcast",
        "custom-call"}, state_ops


def test_no_decode_chunk_of_a_latent_row_family_gathers_its_table(one_chip):
    """The mHC family's decode chunk at the served size (7 MLA layers, 8
    slots of 8,704 positions, 16 steps), compiled for the v5e: every layer
    reads the 624 MB latent pool through the kernel where it lies and the
    chunk writes its side window back where it lies; the program holds no
    gathered ``[L, B, S, W]`` context (561 MB at the parent, once a chunk),
    no layer's ``[B, S, W]``, and no temporary near the pool's size."""
    from distributed_inference_engine_tpu.models import xing as fam
    from distributed_inference_engine_tpu.models.base import unembed

    spec = fam.xing_spec("xing4.0-pp1", max_seq_len=8704)
    slots, n_pages, page, mp, steps = 8, 544, 128, 68, 16
    lanes = spec.cache_row_width
    assert lanes == 640

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_params(spec, jax.random.key(0))))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_state(spec, slots)))
    pool = arr(spec.paged_layers, n_pages, page, lanes, dtype=jnp.bfloat16)
    pool_bytes = spec.paged_layers * n_pages * page * lanes * 2

    def decode(params, pages, state, lengths, last, active, table):
        ctx = fam.decode_context(pages, table, "pallas-decode")
        side = jnp.zeros((spec.paged_layers, slots, steps, lanes),
                         pages.dtype)

        def step(carry, _):
            side, state, now, last = carry
            hidden, side, state, _m = fam.forward_decode_step(
                spec, params, last, now, lengths, ctx, side, state, active)
            tok = jnp.argmax(unembed(spec, params, hidden), -1)
            return (side, state, now + 1, tok.astype(jnp.int32)), tok

        (side, state, now, last), toks = jax.lax.scan(
            step, (side, state, lengths, last), None, length=steps)
        pages = fam.write_rows_into_pages(pages, side, table, now - lengths,
                                          lengths)
        return pages, state, toks

    compiled = jax.jit(decode, donate_argnums=(1, 2)).lower(
        params, pool, state, arr(slots), arr(slots),
        arr(slots, dtype=jnp.bool_), arr(slots, mp)).compile()
    text = compiled.as_text()
    assert text.count("latent_decode_custom_call") >= spec.paged_layers
    for w in (576, 640):
        for shape in (f"[7,8,8704,{w}]", f"[8,8704,{w}]", f"[8,68,128,{w}]",
                      f"[7,8,68,128,{w}]", f"[544,{w}]"):
            assert shape not in text, shape
    # (167 MB today: buffers of the layers' weight matrices, none the pool's)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * pool_bytes


def test_the_sliding_window_family_reads_both_pools_in_place_and_fits(
        one_chip, monkeypatch):
    """The sliding-window family's programs at the served size (12 layers of
    64 experts, 8 slots of 16,896 positions, 16 steps; a prefill at the
    16,384 bucket), compiled for the v5e with the Mosaic grouped matmul:
    every layer of both kinds reads its pool through the K|V kernel where it
    lies, the window pool rides the decode scan with no copy, and the
    largest prefill's temporaries fit beside the 10.9 GB tree and the 1 GB
    of pages in a chip's 15.75 GB. Since PR 40 the prefill's attention is
    the flash kernel in both layer kinds (``flash_prefill.prefill_impl`` is
    steered to the chip's answer here: this process sees the CPU): no
    float32 ``[1, 4, 8, 512, keys]`` score tensor is left in the program,
    and its temporaries are 1.28 GB against 1.83 GB with the XLA body."""
    from distributed_inference_engine_tpu.models import mellum as fam
    from distributed_inference_engine_tpu.models.base import unembed
    from distributed_inference_engine_tpu.ops import flash_prefill

    monkeypatch.setattr(flash_prefill, "prefill_impl",
                        lambda t, dh: "flash")

    spec = fam.mellum_spec("mellum2-12b-a2.5b-pp1", max_seq_len=16896)
    slots, page, mp, steps = 8, 128, 132, 16
    n_pages = slots * mp
    n_window = slots * fam.window_pages_per_slot(spec, page)
    assert n_window == 80 and spec.cache_row_width == 1024

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_params(spec, jax.random.key(0))))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_state(spec, slots, page, n_window, mp)))
    pool = arr(spec.paged_layers, n_pages, page, spec.cache_row_width,
               dtype=jnp.bfloat16)
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        (params, state, pool)))
    assert 11.9e9 < held < 12.0e9

    def decode(params, pages, state, lengths, last, active, table):
        ctx = fam.decode_context(pages, table, "pallas-decode")
        side = jnp.zeros((spec.n_layers, slots, steps,
                          spec.cache_row_width), pages.dtype)

        def step(carry, _):
            side, state, now, last = carry
            hidden, side, state, _m = fam.forward_decode_step(
                spec, params, last, now, lengths, ctx, side, state, active,
                moe_impl="gmm")
            tok = jnp.argmax(unembed(spec, params, hidden), -1)
            return (side, state, now + 1, tok.astype(jnp.int32)), tok

        (side, state, now, last), toks = jax.lax.scan(
            step, (side, state, lengths, last), None, length=steps)
        pages, state = fam.write_side(pages, state, side, table,
                                      now - lengths, lengths)
        return pages, state, toks

    def prefill(params, tokens, lens, pages, state, table, slot_ids):
        hidden, pages, state, _ = fam.forward_prefill_into_pages(
            spec, params, tokens, lens, pages, state, table, slot_ids,
            moe_impl="gmm")
        return hidden[:, -1], pages, state

    dec = jax.jit(decode, donate_argnums=(1, 2)).lower(
        params, pool, state, arr(slots), arr(slots),
        arr(slots, dtype=jnp.bool_), arr(slots, mp)).compile()
    text = dec.as_text()
    assert text.count("flash_decode_custom_call") >= 2     # one a kind
    # neither pool is copied, gathered or sliced by the chunk
    for shape in ("[9,80,128,1024]", "[3,1056,128,1024]"):
        ops = set()
        for line in text.splitlines():
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z-]*)\(", line)
            if m and shape in m.group(1).split(" ")[0]:
                ops.add(m.group(2))
        assert ops and ops <= {"parameter", "tuple", "get-tuple-element",
                               "while", "bitcast", "scatter", "fusion",
                               "custom-call"}, (shape, ops)
    assert dec.memory_analysis().temp_size_in_bytes < 0.6e9
    pre = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, arr(1, 16384), arr(1), pool, state, arr(1, mp),
        arr(1)).compile()
    temp = pre.memory_analysis().temp_size_in_bytes
    assert held + temp < 15.2e9, (held, temp)
    text = pre.as_text()
    # under its layer kind's scope in a sub-scope of its own, never under
    # ``flash_decode`` (the benchmark counts decode steps by that name)
    for kind in ("swa", "full"):
        assert f"attn.{kind}/flash_prefill/" in text
    assert "kv_prefill_flash" in text and "flash_decode" not in text
    assert not re.search(r"f32\[1,4,8,512,[0-9]+\]", text)
    assert temp < 1.5e9, temp          # the parent's: 1,831,853,568


def test_the_learned_selection_family_compiles_and_fits(one_chip,
                                                         monkeypatch):
    """The learned-sparse-attention family's programs at the served size (6
    layers of 128 experts, 8 slots of 33,792 positions, 16 steps; a prefill
    at the 32,768 bucket), compiled for the v5e with the Mosaic grouped
    matmul: the decode chunk's selection is the three kernels of
    ``ops/sparse_index.py``, each reading its pool's LIVE pages where they
    lie (no copy or slice of either pool, no ``lax.top_k``, no row
    gather), and the largest prefill (``flash_prefill.prefill_impl`` is
    steered to the chip's answer here: the masked flash kernel a chunk of
    4,096 queries, one index head's scores at a time) fits its temporaries
    beside the 8.75 GB tree and the 3.53 GB of pages (12.28 GB held) in a
    chip's 15.75 GiB = 16.9 GB: 2.3 GB (with every chunk unrolled and all 16
    heads' score products alive at once it was 4.6 GB and did not)."""
    from distributed_inference_engine_tpu.models import keye as fam
    from distributed_inference_engine_tpu.models.base import unembed
    from distributed_inference_engine_tpu.ops import flash_prefill

    monkeypatch.setattr(flash_prefill, "prefill_impl",
                        lambda t, dh: "flash")

    spec = fam.keye_spec("keye-vl-2.0-30b-a3b-pp1", max_seq_len=33792)
    slots, page, mp, steps = 8, 128, 264, 16
    n_pages = slots * mp
    width = spec.cache_row_width
    assert (width, spec.index_head_dim, spec.index_topk) == (1024, 64, 2048)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_params(spec, jax.random.key(0))))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_state(spec, slots, page, n_pages)))
    pool = arr(spec.paged_layers, n_pages, page, width, dtype=jnp.bfloat16)
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        (params, state, pool)))
    assert 12.2e9 < held < 12.3e9

    def decode(params, pages, state, lengths, last, active, table):
        ctx = fam.decode_context(pages, table, "pallas-decode")
        side = jnp.zeros((spec.n_layers, slots, steps,
                          width + spec.index_head_dim), pages.dtype)

        def step(carry, _):
            side, state, now, last = carry
            hidden, side, state, _m = fam.forward_decode_step(
                spec, params, last, now, lengths, ctx, side, state, active,
                moe_impl="gmm")
            tok = jnp.argmax(unembed(spec, params, hidden), -1)
            return (side, state, now + 1, tok.astype(jnp.int32)), tok

        (side, state, now, last), toks = jax.lax.scan(
            step, (side, state, lengths, last), None, length=steps)
        pages, state = fam.write_side(pages, state, side, table,
                                      now - lengths, lengths)
        return pages, state, toks

    def prefill(params, tokens, lens, pages, state, table, slot_ids):
        hidden, pages, state, _ = fam.forward_prefill_into_pages(
            spec, params, tokens, lens, pages, state, table, slot_ids,
            moe_impl="gmm")
        return hidden[:, -1], pages, state

    dec = jax.jit(decode, donate_argnums=(1, 2)).lower(
        params, pool, state, arr(slots), arr(slots),
        arr(slots, dtype=jnp.bool_), arr(slots, mp)).compile()
    text = dec.as_text()
    # the selection's three kernels, and nothing of the XLA body: no sort
    # (``lax.top_k``), no gather of 2,048 rows a sequence
    for kernel in ("index_scores_decode", "select_mask_decode",
                   "sparse_decode_flash"):
        assert kernel in text, kernel
    assert not [line for line in text.splitlines() if "attn.dsa" in line
                and re.search(r" (sort|topk)\(|top_k|TopK", line)]
    assert "[8,2048,1024]" not in text
    # the K|V pool is read where it lies and scattered into, never copied
    # or sliced a layer
    def ops_over(shape_rx):
        found = []
        for line in text.splitlines():
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z-]*)\(",
                         line)
            if m and re.search(shape_rx, m.group(1).split(" ")[0]):
                found.append((m.group(2), line))
        return found

    ops = {op for op, _ in ops_over(r"\[(6,2112|12672),128,1024\]")}
    assert ops and ops <= {"parameter", "tuple", "get-tuple-element",
                           "while", "bitcast", "scatter", "fusion"}, ops
    # the index keys' pool: the kernel's view of a page (its positions on
    # the lanes) is a BITCAST of how the pool lies on a TPU (the longer of
    # its last two axes minor), inside the steps' loop; the only copies are
    # the write-back's two, once a chunk (ROADMAP S19 (c))
    index = ops_over(r"\[(6,2112,128,64|12672,64,128)\]")
    assert any(op == "bitcast" and "attn.index/transpose" in line
               for op, line in index)
    copies = [line for op, line in index if op == "copy"]
    assert len(copies) <= 2 and not any("while" in c for c in copies)
    assert {op for op, _ in index} <= {
        "parameter", "tuple", "get-tuple-element", "while", "bitcast",
        "scatter", "fusion", "copy"}
    assert dec.memory_analysis().temp_size_in_bytes < 1.0e9
    pre = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, arr(1, 32768), arr(1), pool, state, arr(1, mp),
        arr(1)).compile()
    temp = pre.memory_analysis().temp_size_in_bytes
    assert temp < 2.6e9 and held + temp < 15.0e9, (held, temp)
    text = pre.as_text()
    # the kernels, each run's shape in its name (what a profile's reader
    # counts the first two's work by: perfbench/lib/scopes_dsa.py)
    assert "sparse_prefill_flash_b1q4096k32768" in text
    assert "index_scores_flash_b1q512k32768" in text
    # between them the top-2,048 of a block of queries, one kernel: no
    # counted threshold in XLA (its keys were u32[1,512,32768])
    assert "select_mask_flash_b1q512k32768" in text
    assert "u32[1,512,32768]" not in text
    assert "attn.dsa/attn.sparse/" in text or "attn.sparse" in text
    # no float32 score tensor of every head of a block of queries
    assert not re.search(r"f32\[1,4,8,\d+,32768\]", text)


def test_latent_decode_at_64_heads_and_32_rows_compiles_for_v5e(one_chip):
    """The latent kernel at the Kimi cell's shape: 64 query heads on the
    sublanes (twice the other two families'), 32 rows of 60 pages, the
    7-layer pool of 1,920 pages a layer read where it lies."""
    from distributed_inference_engine_tpu.ops.flash_decode import (
        latent_decode_attention_pallas)

    b, mp, w, layers, p, h, lanes, rank = 32, 60, 16, 7, 128, 64, 640, 512
    n = b * mp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pages, pt, plen, side, n_side, layer):
        return latent_decode_attention_pallas(
            q, pages, pt, plen, side, n_side, layer, v_lanes=rank,
            scale=0.1, n_pages_per_layer=n)

    bf = jnp.bfloat16
    compiled = jax.jit(fn).lower(
        sds((b, h, lanes), bf), sds((layers * n, p, lanes), bf),
        sds((b, mp), jnp.int32), sds((b,), jnp.int32), sds((b, w, lanes), bf),
        sds((b,), jnp.int32), sds((), jnp.int32)).compile()
    assert "latent_decode_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_the_expert_share_family_fits_with_32_slots_and_its_held_rows(
        one_chip, monkeypatch):
    """The plain-residual MLA family's programs at the served size (Kimi-K2.5
    as one chip of EP32: 7 layers of d 7168, 64 heads, 12 of 384 experts, 32
    slots of 7,680 positions, 16 steps; a prefill at the 6,144 bucket),
    compiled for the v5e with the Mosaic grouped matmul and the latent
    prefill kernel steered in (this process sees the CPU): every layer reads
    the 2.2 GB latent pool through the kernel where it lies, and the
    largest prefill's temporaries fit beside the 9.73 GB tree and the pool
    in a chip's 15.75 GB because the expert layer runs over its HELD
    assignments in blocks of 2,048 rows (``moe_block_held``: 1.11 GB of
    temporaries; with ``moe_block``'s 49,152 rows a layer 3.08 GB, 15.0 GB
    in all, PR 41)."""
    from distributed_inference_engine_tpu.models import xing as fam
    from distributed_inference_engine_tpu.models.base import unembed
    from distributed_inference_engine_tpu.ops import mla

    monkeypatch.setattr(mla, "prefill_impl", lambda t: "flash")
    spec = fam.kimi_spec("kimi-k2.5-ep32-pp1", max_seq_len=7680)
    slots, page, mp, steps = 32, 128, 60, 16
    n_pages = slots * mp
    lanes = spec.cache_row_width
    assert lanes == 640

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def arr(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_params(spec, jax.random.key(0))))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.init_state(spec, slots)))
    pool = arr(spec.paged_layers, n_pages, page, lanes, dtype=jnp.bfloat16)
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        (params, state, pool)))
    assert 11.9e9 < held < 12.0e9

    def decode(params, pages, state, lengths, last, active, table):
        ctx = fam.decode_context(pages, table, "pallas-decode")
        side = jnp.zeros((spec.paged_layers, slots, steps, lanes),
                         pages.dtype)

        def step(carry, _):
            side, state, now, last = carry
            hidden, side, state, _m = fam.forward_decode_step(
                spec, params, last, now, lengths, ctx, side, state, active,
                moe_impl="gmm")
            tok = jnp.argmax(unembed(spec, params, hidden), -1)
            return (side, state, now + 1, tok.astype(jnp.int32)), tok

        (side, state, now, last), toks = jax.lax.scan(
            step, (side, state, lengths, last), None, length=steps)
        pages = fam.write_rows_into_pages(pages, side, table, now - lengths,
                                          lengths)
        return pages, state, toks

    def prefill(params, tokens, lens, pages, state, table, slot_ids):
        hidden, pages, state, _ = fam.forward_prefill_into_pages(
            spec, params, tokens, lens, pages, state, table, slot_ids,
            moe_impl="gmm")
        return hidden[:, -1], pages, state

    dec = jax.jit(decode, donate_argnums=(1, 2)).lower(
        params, pool, state, arr(slots), arr(slots),
        arr(slots, dtype=jnp.bool_), arr(slots, mp)).compile()
    text = dec.as_text()
    assert text.count("latent_decode_custom_call") >= spec.paged_layers
    for shape in ("[7,32,7680,640]", "[32,7680,640]", "[32,60,128,640]",
                  "[7,32,60,128,640]"):
        assert shape not in text, shape
    assert dec.memory_analysis().temp_size_in_bytes < 0.6e9
    pre = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, arr(1, 6144), arr(1), pool, state, arr(1, mp),
        arr(1)).compile()
    temp = pre.memory_analysis().temp_size_in_bytes
    # no tensor of all 6,144 x 8 assignments' rows: blocks of 2,048
    assert temp < 1.5e9 and held + temp < 13.5e9, (held, temp)
    text = pre.as_text()
    assert "[49152,7168]" not in text and "[49152,4096]" not in text
    assert "[2048,7168]" in text


# D, F, experts a layer holds, rows an expert of the call
@pytest.mark.parametrize("d,f,e,rows", [
    (2304, 896, 64, 512), (2304, 896, 64, 256),     # Mellum: a 4,096 / 2,048
    (2048, 768, 128, 2048), (2048, 768, 128, 256),  # Keye: 32,768 / 4,096
    (3584, 1024, 64, 512), (3584, 1024, 64, 256),   # Xing: 8,192 / 4,096
    (2560, 768, 128, 512), (7168, 2048, 12, 512),   # Ling's, Kimi's widths
    (2304, 896, 64, 1), (7168, 2048, 12, 10),       # decode steps
])
def test_the_grouped_products_tiles_compile_for_v5e(one_chip, d, f, e, rows):
    """The Mosaic grouped matmul at the tiles ``gmm_tiling`` resolves, both
    products of every served width pair: the compiler's own VMEM (a spilled
    operand tile, the float32 product) has to fit beside what
    ``gmm_vmem_bytes`` counts, inside the default scoped limit."""
    from distributed_inference_engine_tpu.ops import moe_routed as mr

    m = -(-rows * e // 128) * 128
    for k, n in ((d, 2 * f), (f, d)):
        tm, tk, tn = mr.gmm_tiling(m, k, n, e)
        if rows >= mr.GMM_PREFILL_ROWS:
            assert tk == k, (tm, tk, tn)
        compiled = jax.jit(
            lambda lhs, rhs, sizes: mr.grouped_matmul(lhs, rhs, sizes, "gmm",
                                                      e)).lower(
            jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((e, k, n), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip)
        ).compile()
        assert "tpu_custom_call" in compiled.as_text()
