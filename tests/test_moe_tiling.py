"""The tiles of the grouped expert product (``ops/moe_routed.py``
``gmm_tiling``): few rows an expert keep the decode tiles to the digit, a
prefill's rows take an expert's K whole inside a stated VMEM budget, the
callers pad / block their sorted rows to the row tile the product uses, and
the Mosaic kernel through the interpreter gives ``lax.ragged_dot``'s rows
under the prefill's tiles."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax import lax  # noqa: E402

from distributed_inference_engine_tpu import models  # noqa: E402
from distributed_inference_engine_tpu.models import mellum  # noqa: E402
from distributed_inference_engine_tpu.ops import moe_routed as mr  # noqa: E402

CONFIGS = Path(__file__).resolve().parent.parent / "perfbench" / "configs"

# family -> (D, F, experts a layer holds, top-k, the tiles every call ran
# before PR 52: gate|up, down)
SERVED = {
    "mellum": (2304, 896, 64, 8, (128, 1152, 256), (128, 896, 768)),
    "keye": (2048, 768, 128, 8, (128, 1024, 768), (128, 768, 512)),
    "xing": (3584, 1024, 64, 4, (128, 896, 512), (128, 1024, 512)),
    "ling": (2560, 768, 128, 8, (128, 1280, 768), (128, 768, 640)),
    "kimi": (7168, 2048, 12, 8, (128, 1024, 512), (128, 1024, 512)),
}
# the seven served configurations: file -> family ("" = no routed experts)
CELLS = {
    "mistral-7b-int4": "", "olmo-hybrid-7b-pp2": "",
    "ling-3.0-flash-ep4": "ling", "xing4.0-29b-a4b-pp1": "xing",
    "mellum2-12b-a2.5b-pp1": "mellum", "kimi-k2.5-ep32-pp1": "kimi",
    "keye-vl-2.0-30b-a3b-pp1": "keye",
}


def _serve(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["serve"]


def _spec(name):
    serve = _serve(name)
    return models.spec_for_architecture(serve["architecture"], serve["size"])


@pytest.mark.parametrize("family", sorted(SERVED))
@pytest.mark.parametrize("rows", [8, 16, 32, 128])
def test_a_decode_sized_call_keeps_its_tiles_to_the_digit(family, rows):
    d, f, e, k, gate_up, down = SERVED[family]
    m = -(-rows * k // 128) * 128            # ``moe_block``'s padding
    assert mr.gmm_tiling(m, d, 2 * f, e) == gate_up
    assert mr.gmm_tiling(m, f, d, e) == down


@pytest.mark.parametrize("family", sorted(SERVED))
def test_a_prefill_sized_call_takes_k_whole_inside_the_budget(family):
    d, f, e, k, gate_up, down = SERVED[family]
    for rows in (mr.GMM_PREFILL_ROWS, 512, 2048):
        m = rows * e
        for kk, n, few in ((d, 2 * f, gate_up), (f, d, down)):
            tm, tk, tn = mr.gmm_tiling(m, kk, n, e)
            assert tm == 128 and n % tn == 0
            assert tk == kk, "the weight block must repeat across row tiles"
            assert tn >= 256, "the lhs tile re-read once for every N tile"
            assert mr.gmm_vmem_bytes(tm, tk, tn) <= mr.GMM_VMEM_BUDGET
            # the widest that fits: the next divisor up does not
            wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
            assert all(mr.gmm_vmem_bytes(tm, tk, t) > mr.GMM_VMEM_BUDGET
                       for t in wider)
    assert mr.GMM_VMEM_BUDGET < mr.GMM_SCOPED_VMEM
    # one row an expert short of a prefill's: the decode tiles
    m = (mr.GMM_PREFILL_ROWS - 1) * e // 128 * 128
    assert mr.gmm_tiling(m, d, 2 * f, e) == gate_up


def test_a_stacked_trees_other_layers_do_not_count_as_experts():
    """Mellum's ``rhs`` holds three periods' experts: the rows an expert are
    the call's over THIS layer's 64, not over 192."""
    d, f, e, k, gate_up, _ = SERVED["mellum"]
    m = mr.GMM_PREFILL_ROWS * e
    assert mr.gmm_tiling(m, d, 2 * f, e)[1] == d
    assert mr.gmm_tiling(m, d, 2 * f, 3 * e) == gate_up


def _product_rows(spec, family, n):
    """The rows ``m`` of each grouped product a program over ``n`` tokens
    runs, and the experts they are dealt to."""
    k, held = spec.experts_per_token, spec.experts_held[1]
    if mr.moe_body(spec) is mr.moe_block_held:
        return mr.held_block_rows(spec, n), held
    if family in ("mellum", "keye"):           # ``mellum._moe``'s parts
        n //= mellum.moe_parts(n)
    m = n * k
    return m + -m % (128 if m >= 128 else 16), held


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_bucket_of_a_served_configuration_is_whole_row_tiles(cell):
    serve, family = _serve(cell), CELLS[cell]
    spec = _spec(cell)
    if not family:
        assert not spec.n_experts, "a dense cell: no grouped product"
        return
    d, f, e, k, gate_up, down = SERVED[family]
    assert (spec.d_model, spec.moe_d_ff, spec.experts_held[1],
            spec.experts_per_token) == (d, f, e, k)
    slots = serve["max_batch_size"]
    for n in list(serve["prefill_buckets"]) + [slots]:
        m, held = _product_rows(spec, family, n)
        for kk, nn in ((d, 2 * f), (f, d)):
            tm, tk, tn = mr.gmm_tiling(m, kk, nn, held)
            assert m % tm == 0, (cell, n, m, tm)
            assert mr.gmm_vmem_bytes(tm, tk, tn) <= mr.GMM_VMEM_BUDGET
    # the decode step is today's program: its tiles did not move (8 slots
    # x 8 choices are ONE block of 64 rows)
    m, held = _product_rows(spec, family, slots)
    assert m == min(-(-slots * k // 16) * 16, 128) or family == "kimi"
    assert mr.gmm_tiling(m, d, 2 * f, held) == (min(m, 128),) + gate_up[1:]
    assert mr.gmm_tiling(m, f, d, held) == (min(m, 128),) + down[1:]


def test_the_served_prefills_that_engage():
    """Which buckets run the prefill's tiles: the table of PERF §6 (PR 52).
    Ling's and Kimi's never do (few rows a held expert)."""
    engaged = {}
    for cell, family in CELLS.items():
        if not family:
            continue
        spec, d = _spec(cell), SERVED[family][0]
        engaged[family] = []
        for n in _serve(cell)["prefill_buckets"]:
            m, held = _product_rows(spec, family, n)
            if mr.gmm_tiling(m, d, 2 * spec.moe_d_ff, held)[1] == d:
                engaged[family].append(n)
    assert engaged == {
        "mellum": [2048, 4096, 8192, 16384],
        "keye": [4096, 8192, 16384, 32768],
        "xing": [4096, 8192], "ling": [], "kimi": []}


@pytest.mark.parametrize("case", ["uneven", "empty_groups", "rows_past_sum",
                                  "expert_offset_stack"])
def test_the_kernel_under_the_prefills_tiles_gives_ragged_dots_rows(
        case, monkeypatch):
    """Small widths, the prefill's tiling rule (the threshold and the row
    tile cut so that 64 rows an expert are a prefill's): the interpreted
    Mosaic kernel against ``lax.ragged_dot`` on the rows that belong to a
    group."""
    monkeypatch.setattr(mr, "GMM_PREFILL_ROWS", 64)
    e, k, n = 4, 256, 512
    sizes = {"uneven": [300, 17, 130, 65], "empty_groups": [0, 384, 0, 128],
             "rows_past_sum": [100, 40, 0, 90],
             "expert_offset_stack": [200, 56, 128, 128]}[case]
    m = 512
    groups = jnp.asarray(sizes, jnp.int32)
    live = e
    if case == "expert_offset_stack":
        # three layers' experts one after another, this layer's the middle
        groups = jnp.zeros((3 * e,), jnp.int32).at[e:2 * e].set(groups)
    ks = jax.random.split(jax.random.key(len(case)), 2)
    lhs = jax.random.normal(ks[0], (m, k), jnp.float32).astype(jnp.bfloat16)
    rhs = (0.05 * jax.random.normal(ks[1], (groups.shape[0], k, n))
           ).astype(jnp.bfloat16)
    tm, tk, tn = mr.gmm_tiling(m, k, n, live)
    assert (tm, tk, tn) == (128, k, n) and m % tm == 0
    got = mr.grouped_matmul(lhs, rhs, groups, "gmm_interpret", live)
    ref = lax.ragged_dot(lhs, rhs, groups,
                         preferred_element_type=jnp.float32)
    rows = sum(sizes)
    assert got.dtype == jnp.float32 and got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got[:rows]), np.asarray(ref[:rows]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_tokens", [96, 192])
def test_moe_block_under_the_prefills_tiles_gives_the_xla_paths_rows(
        n_tokens, monkeypatch):
    """``moe_block`` end to end through the interpreted kernel with the
    prefill's tiles engaged against the XLA path: 96 tokens x 2 choices =
    192 rows are padded to two 128-row tiles, 192 tokens are three."""
    monkeypatch.setattr(mr, "GMM_PREFILL_ROWS", 16)
    # the tiny spec's routing (softmax, top-2 of 8) at widths of whole
    # 128-lane tiles
    spec = mellum.mellum_spec("mellum-tiny", dtype="float32")
    e, d, f = spec.n_experts, 128, 64
    ks = jax.random.split(jax.random.key(3), 4)
    blk = {"w_router": jax.random.normal(ks[0], (d, e), jnp.float32),
           "w_gate_up": 0.1 * jax.random.normal(ks[1], (e, d, 2 * f)),
           "w_down": 0.1 * jax.random.normal(ks[2], (e, f, d))}
    x = jax.random.normal(ks[3], (n_tokens, d), jnp.float32)
    valid = jnp.arange(n_tokens) < n_tokens - 7
    m = -(-n_tokens * spec.experts_per_token // 128) * 128
    assert mr.gmm_tiling(m, d, 2 * f, e) == (128, d, 2 * f)
    want, cw = mr.moe_block(spec, blk, x, valid, "xla")
    got, cg = mr.moe_block(spec, blk, x, valid, "gmm_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cg), np.asarray(cw))
