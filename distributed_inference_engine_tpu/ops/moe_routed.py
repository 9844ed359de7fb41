"""Routed experts of a hybrid spec (DeepSeek-V3's ``noaux_tc``), as ONE
chip of an expert-parallel deployment computes them.

Routing, float32, over ALL ``n_experts``: ``s = sigmoid(x W_r)``; choice
scores ``s + b`` (``b`` the expert bias); ``n_group`` groups, a group's
score the sum of its two largest ``s + b``, the ``topk_group`` best groups
kept; top-k of ``s + b`` inside them; gates ``s`` at the chosen experts over
their sum, times ``routed_scaling_factor``. A spec whose ``moe_scoring`` is
``softmax`` (Mellum 2, the Qwen-MoE convention) has neither bias nor groups:
``p = softmax(x W_r)`` over all experts, the ``k`` largest, gates ``p`` at
the chosen experts over their sum (``norm_topk_prob``).

This chip holds experts ``[first, first + count)`` (``spec.experts_held``)
and computes their gate-weighted part of the result, plus the shared expert
once where the spec has one (``shared_d_ff``); what the absent experts would
add is left out. No capacity, nothing
dropped. The product is GROUPED: the (token, choice) pairs that landed on a
held expert are sorted by expert and each expert multiplies only its own
rows (``grouped_matmul``), so operations follow the routed tokens and, in
decode, the weight bytes read follow the experts that got one. In a prefill
an expert gets hundreds of rows and the product is bound by operations, if
the expert's matrix crosses HBM once and not once for every row tile: the
kernel's tiles follow the rows an expert can expect (``gmm_tiling``: from
the call's shapes, K whole inside a VMEM budget where a prefill's rows
arrive, the decode step's tiles unchanged where few do).

Two bodies, chosen from the spec (``moe_body``). ``moe_block`` gathers and
multiplies rows for ALL ``N x k`` assignments and masks the ones not held:
harmless where the chip holds a quarter of the experts or all of them.
``moe_block_held`` is for a chip that holds a fraction of ONE routing group
(``held_fraction_of_one_group``: 12 of 384, so 3 % of the assignments are
held and most tokens send none): the sort puts the held assignments first,
and the gather, the two grouped products and the sum back to tokens run over
those alone, in row blocks of a fixed size under a loop whose trip count
follows the held count. Exact, nothing dropped, shapes static.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def route(spec, x: jnp.ndarray, w_router: jnp.ndarray, bias=None
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [N, D] -> (expert ids [N, k] int32, gates [N, k] float32).
    ``bias`` is the sigmoid scoring's expert bias; softmax takes none."""
    e, ng, k = spec.n_experts, spec.n_group, spec.experts_per_token
    logits = jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    if spec.moe_scoring == "softmax":
        g, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return idx.astype(jnp.int32), g / jnp.sum(g, axis=-1, keepdims=True)
    s = jax.nn.sigmoid(logits)
    c = s + bias.astype(jnp.float32)
    top2, _ = lax.top_k(c.reshape(-1, ng, e // ng), 2)
    _, groups = lax.top_k(top2.sum(-1), spec.topk_group)       # [N, topk_g]
    keep = jnp.zeros((c.shape[0], ng), bool).at[
        jnp.arange(c.shape[0])[:, None], groups].set(True)
    c = jnp.where(jnp.repeat(keep, e // ng, axis=1), c, -jnp.inf)
    _, idx = lax.top_k(c, k)
    g = jnp.take_along_axis(s, idx, axis=1)
    g = g / jnp.sum(g, axis=-1, keepdims=True) * spec.routed_scaling_factor
    return idx.astype(jnp.int32), g


# The scoped VMEM a Mosaic kernel gets on this chip unless it asks for more
# (megablox asks for nothing), and the part of it the grouped product's
# tiles may take: the compiler keeps vector temporaries of its own there (a
# spilled operand tile, the float32 product before it is added), which
# ``gmm_vmem_bytes`` does not see: (256, 7168, 256) counts 14.75 MiB and is
# refused at 18. ``tests/test_tpu_compile.py`` compiles every served width
# pair's tiles for the v5e.
GMM_SCOPED_VMEM = 16 * 2 ** 20
GMM_VMEM_BUDGET = GMM_SCOPED_VMEM * 3 // 4
# rows an expert (a call's rows over the experts that can hold them) from
# which a call is a prefill's: two row tiles' worth. Alone on the chip K
# whole wins from 64 rows an expert up
# (``docs/sweeps/pr52-gmm-prefill-tiles.txt``); 256 is the least that leaves
# every call with few REAL rows an expert as it was: a decode step, Ling's
# prefills (128 by the shapes, a quarter of them held), Kimi's held blocks
# (171 at most).
GMM_PREFILL_ROWS = 256


def gmm_vmem_bytes(tm: int, tk: int, tn: int) -> int:
    """VMEM the Mosaic grouped matmul's pipeline holds at these tiles: the
    bfloat16 lhs and rhs tiles and the float32 out tile, two buffers each,
    and the float32 accumulator."""
    return 2 * (2 * tm * tk + 2 * tk * tn + 4 * tm * tn) + 4 * tm * tn


def gmm_widest_n_tile(tm: int, k: int, n: int) -> int:
    """The widest N tile (a multiple of 128 that divides ``n``) that fits
    ``GMM_VMEM_BUDGET`` beside K whole and a row tile of ``tm``; 0 where
    none does."""
    return max((t for t in range(128, n + 1, 128) if n % t == 0
                and gmm_vmem_bytes(tm, k, t) <= GMM_VMEM_BUDGET), default=0)


def gmm_tiling(m: int, k: int, n: int, groups: int) -> Tuple[int, int, int]:
    """Tiles ``(tm, tk, tn)`` of the Mosaic grouped matmul (grid: N tiles x
    the row tiles that hold rows x K tiles, K innermost) for ``m`` sorted
    rows over ``groups`` experts, from the shapes alone. Whole row blocks up
    to 128 in every case (what the callers pad and block to).

    Few rows an expert (a decode step; a prefill that sends a held expert a
    handful): the product is bound by the weight bytes of the experts
    touched, every tile of which is read once. The largest K tile up to
    1,280 and N tile up to 768 (multiples of 128 that divide), so a step's
    DMA (0.6 MB of weights at Mellum's widths, 2 MB at Ling's) is long
    against its issue cost.

    ``GMM_PREFILL_ROWS`` rows an expert or more (a prefill): an expert's
    rows span several row tiles, and the pipeline fetches a weight tile
    again at every grid step whose block index differs from the last one's,
    which with two K tiles or more is every step: the expert's matrix would
    cross HBM once for every 128 rows. So K is taken whole (the weight block
    then repeats across the expert's row tiles and is fetched once) with the
    widest N tile that fits ``GMM_VMEM_BUDGET`` (the lhs tile is re-read
    once for every N tile). The row tile stays 128: alone on the chip 256
    rows read within 2 % of 128 at the same N tile and lose where the budget
    then narrows it, 512 lose 15 %. Where K whole fits beside no N tile the
    few-rows tiles stand."""
    def tile(dim: int, cap: int) -> int:
        best = 128 if dim % 128 == 0 else dim
        for t in range(128, min(dim, cap) + 1, 128):
            if dim % t == 0:
                best = t
        return best

    tm = min(m, 128)
    if m // groups >= GMM_PREFILL_ROWS:
        tn = gmm_widest_n_tile(tm, k, n)
        if tn:
            return tm, k, tn
    return tm, tile(k, 1280), tile(n, 768)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, impl: str,
                   experts: int = 0) -> jnp.ndarray:
    """Rows of ``lhs`` [M, K] sorted by group; group g's rows times
    ``rhs[g]`` [G, K, N]. Rows past ``sum(group_sizes)`` come back as
    unspecified values (the caller masks them). float32 out. ``experts``:
    how many of the G groups can hold rows (a stacked tree's other layers
    hold none); 0 = all. ``M`` is whole blocks of 128 rows, or fewer rows.

    ``impl``: "xla" = ``jax.lax.ragged_dot``; "gmm" = the Mosaic grouped
    matmul (``jax.experimental.pallas.ops.tpu.megablox``), whose grid visits
    only row tiles x groups that hold rows; "gmm_interpret" = the same
    kernel through the interpreter (tests)."""
    if impl == "xla":
        return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    with jax.named_scope("gmm"):
        return gmm(lhs, rhs, group_sizes.astype(jnp.int32), jnp.float32,
                   gmm_tiling(m, k, rhs.shape[2], experts or rhs.shape[0]),
                   interpret=impl == "gmm_interpret")


def default_impl() -> str:
    return "gmm" if jax.default_backend() == "tpu" else "xla"


# what the three counters of ``moe_block`` / ``moe_block_held`` are called
# where an engine sums them (``models.base.layered_family``)
COUNTERS = ("moe.assignments_held", "moe.assignments_total",
            "moe.experts_touched")
# ... of a prefill: the experts touched are reported of decode steps alone
PREFILL_COUNTERS = COUNTERS[:2] + (None,)


def _swiglu(x, w_gate_up, w_down):
    gu = jnp.einsum("nd,df->nf", x, w_gate_up,
                    preferred_element_type=jnp.float32)
    gate, up = jnp.split(gu, 2, axis=-1)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.einsum("nf,fd->nd", h, w_down,
                      preferred_element_type=jnp.float32)


def moe_block(spec, blk: Dict[str, jnp.ndarray], x: jnp.ndarray,
              valid: jnp.ndarray, impl: str = "", expert_offset=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [N, D], ``valid`` [N] bool (pad rows and rows not live route
    nowhere). Returns (out [N, D] in x's dtype, counters int32 [3]:
    assignments on held experts, assignments in all, held experts with a
    row). ``expert_offset`` (a traced int32): ``w_gate_up`` / ``w_down``
    hold SEVERAL layers' experts one after another (a tree stacked over
    periods and scanned, ``models/mellum.py``) and this layer's begin
    there; the other layers' are groups of no rows, which the grouped
    product does not visit, so no layer's matrices are sliced out (a copy
    of 0.8 GB a layer a step at Mellum's widths)."""
    impl = impl or default_impl()
    n, d = x.shape
    k = spec.experts_per_token
    with jax.named_scope("moe.route"):
        gates, on, order, sizes = _sort_by_held_expert(spec, blk, x, valid)
        m = n * k
        tm = 128 if m >= 128 else -(-m // 16) * 16
        pad = -m % tm
        tok = jnp.pad(order // k, (0, pad))
        row_ok = jnp.arange(m + pad) < jnp.sum(sizes)
        groups = sizes
        if expert_offset is not None:
            groups = lax.dynamic_update_slice(
                jnp.zeros((blk["w_gate_up"].shape[0],), sizes.dtype), sizes,
                (expert_offset,))
    with jax.named_scope("moe.experts"):
        rows = x[tok]                                          # [M, D]
        held = sizes.shape[0]     # ``groups`` may count other layers' too
        gu = grouped_matmul(rows, blk["w_gate_up"], groups, impl, held)
        gate, up = jnp.split(gu, 2, axis=-1)
        h = jnp.where(row_ok[:, None], jax.nn.silu(gate) * up, 0.0)
        y = grouped_matmul(h.astype(x.dtype), blk["w_down"], groups, impl,
                           held)
        g_sorted = jnp.pad(gates.reshape(-1)[order], (0, pad))
        y = jnp.where(row_ok[:, None], y * g_sorted[:, None], 0.0)
        # back to (token, choice) order, then the k choices of a token add
        inv = jnp.argsort(order)
        routed = y[inv].reshape(n, k, d).sum(axis=1)
    return _with_shared(spec, blk, x, routed, on, valid, sizes)


def _sort_by_held_expert(spec, blk, x, valid):
    """Route, then sort the (token, choice) pairs by held expert, the ones
    not held (or of rows not ``valid``) last: (gates [N, k], ``on`` [N, k]
    bool: the pair landed on a held expert, ``order`` [N*k]: the sort,
    ``sizes`` [held]: pairs an expert)."""
    first, held = spec.experts_held
    idx, gates = route(spec, x, blk["w_router"], blk.get("router_bias"))
    local = idx - first
    on = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(on, local, held).reshape(-1)               # [N*k]
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held]
    return gates, on, order, sizes


def _with_shared(spec, blk, x, routed, on, valid, sizes):
    """(routed + the shared expert in x's dtype, the three counters)."""
    shared = None
    if spec.shared_d_ff:
        with jax.named_scope("moe.shared"):
            shared = _swiglu(x, blk["ws_gate_up"], blk["ws_down"])
    with jax.named_scope("moe.combine"):
        counters = jnp.stack([
            jnp.sum(on), jnp.sum(valid) * spec.experts_per_token,
            jnp.sum(sizes > 0)]).astype(jnp.int32)
        out = routed if shared is None else routed + shared
        return out.astype(x.dtype), counters


def held_fraction_of_one_group(spec) -> bool:
    """The chip holds some, not all, experts of a spec whose router has ONE
    group: nothing confines a token's choices to what is held here."""
    return spec.n_group == 1 and 0 < spec.experts_held[1] < spec.n_experts


def moe_body(spec):
    """The expert layer's body, from the spec: over the held assignments
    only where the chip holds a fraction of ONE routing group (most tokens
    then send it nothing), ``moe_block`` (all N x k assignments, the ones not
    held masked) where it holds whole groups or everything. Whether the
    second still wins anywhere is not measured (ROADMAP S16 (d))."""
    return moe_block_held if held_fraction_of_one_group(spec) else moe_block


HELD_BLOCK_MAX = 2048


def held_block_rows(spec, n: int) -> int:
    """Rows of one block of ``moe_block_held`` for ``n`` tokens: twice what
    uniform routing sends the held experts, in whole 128-row tiles (the
    grouped product's row tile) up to ``HELD_BLOCK_MAX`` (a block re-reads
    every touched expert: large blocks keep a long prefill's products
    compute-bound), never past all ``n x k`` assignments. A decode step of
    32 rows holds ~8 assignments in its one block of 128; a skewed batch
    takes more trips, not more room."""
    m = n * spec.experts_per_token
    twice = 2 * m * spec.experts_held[1] // spec.n_experts
    r = min(max(-(-twice // 128) * 128, 128), HELD_BLOCK_MAX)
    return min(r, -(-m // 16) * 16)


def moe_block_held(spec, blk: Dict[str, jnp.ndarray], x: jnp.ndarray,
                   valid: jnp.ndarray, impl: str = ""
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``moe_block``'s result and counters at the cost of the HELD
    assignments: x [N, D] -> (out [N, D], counters int32 [3]). Sorted by
    held expert the held assignments come first; block j takes sorted rows
    ``[j R, (j + 1) R)``, its group sizes the part of each expert's run
    inside it, and adds its gate-weighted rows to their tokens in float32.
    The loop ends with the held count, so a batch in which no token is held
    multiplies nothing and one in which every token is runs ``N k / R``
    blocks. Temporaries are ``R`` rows long whatever ``N`` is."""
    impl = impl or default_impl()
    n, d = x.shape
    k = spec.experts_per_token
    r = held_block_rows(spec, n)
    with jax.named_scope("moe.route"):
        gates, on, order, sizes = _sort_by_held_expert(spec, blk, x, valid)
        n_held = jnp.sum(sizes)
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        pad = -(n * k) % r
        tok = jnp.pad(order // k, (0, pad))
        g_sorted = jnp.pad(gates.reshape(-1)[order], (0, pad))

    def block(carry):
        j, acc = carry
        lo = j * r
        t = lax.dynamic_slice(tok, (lo,), (r,))
        g = lax.dynamic_slice(g_sorted, (lo,), (r,))
        row_ok = lo + jnp.arange(r) < n_held
        groups = jnp.clip(jnp.minimum(ends, lo + r) - jnp.maximum(starts, lo),
                          0, r)
        gu = grouped_matmul(x[t], blk["w_gate_up"], groups, impl)
        gate, up = jnp.split(gu, 2, axis=-1)
        h = jnp.where(row_ok[:, None], jax.nn.silu(gate) * up, 0.0)
        y = grouped_matmul(h.astype(x.dtype), blk["w_down"], groups, impl)
        y = jnp.where(row_ok[:, None], y * g[:, None], 0.0)
        # the held choices of a token add; rows past the held count add 0
        return j + 1, acc.at[t].add(y)

    with jax.named_scope("moe.experts"):
        _, routed = lax.while_loop(
            lambda c: c[0] * r < n_held, block,
            (jnp.int32(0), jnp.zeros((n, d), jnp.float32)))
    return _with_shared(spec, blk, x, routed, on, valid, sizes)
