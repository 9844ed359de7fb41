"""The hybrid family (``models/ling.py``: KDA linear attention with a
recurrent state beside MLA latent attention, sigmoid group-limited routed
experts of which this chip holds a share) against the plain float32
reference the benchmark judges it by (``perfbench/reference/
bailing_hybrid.py``), at the tiny size, on the CPU.

Tolerances. With the served tree widened to float32 and matmuls at highest
precision the two implementations differ by rounding order alone: logits of
magnitude ~0.6 agree to 2e-4 (seen: 3e-6). Every mutation below moves them
by at least five times the bound: a bfloat16 state over a few dozen decode
steps by 1.5e-3, a changed gate, a dropped bias, scaling factor or shared
expert by 1e-2 and more. Served in bfloat16 the same
comparison reads 2-3 % of max|logit| (activations re-rounded through six
layers); 6 % bounds it.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from distributed_inference_engine_tpu.engine.paged_kv import (  # noqa: E402
    PagedKVCache,
)
from distributed_inference_engine_tpu.models import ling  # noqa: E402
from distributed_inference_engine_tpu.models.base import unembed  # noqa: E402
from distributed_inference_engine_tpu.ops import (  # noqa: E402
    kda, mla, moe_routed,
)
from perfbench.lib import families  # noqa: E402

F32_TOL = 2e-4
BF16_TOL = 0.06          # of max|logit|

with open(os.path.join(ROOT, "perfbench", "rehearse", "ling-tiny.json")) as _f:
    CFG = json.load(_f)
REF = families.reference(CFG)


def tiny_spec(**kw):
    return ling.ling_spec("ling-tiny", max_seq_len=128, **kw)


@pytest.fixture(scope="module")
def served_bf16():
    return ling.init_params(tiny_spec(), jax.random.key(7))


@pytest.fixture(scope="module")
def served_f32(served_bf16):
    return jax.tree.map(lambda a: a.astype(jnp.float32), served_bf16)


class Served:
    """The serving programs driven by hand through ``PagedKVCache``: prefill
    at a padded bucket, then teacher-forced decode chunks through the latent
    pages and the per-slot state, collecting every position's logits."""

    def __init__(self, spec, params, slots=4, page=16, pages=32,
                 state_dtype=None, family=ling, attn_impl="xla"):
        self.spec, self.params, self.page = spec, params, page
        self.family = family       # the per-layer family's module
        self.attn_impl = attn_impl
        self.kv = PagedKVCache(spec, max_slots=slots, page_size=page,
                               num_pages=pages, max_seq_len=128,
                               dtype=spec.dtype)
        self.state_dtype = state_dtype
        self.moe = np.zeros(3, np.int64)

    def prefill(self, prompts, bucket):
        n = len(prompts)
        bb = 1 << (n - 1).bit_length()
        slots = [self.kv.alloc_slot(len(p)) for p in prompts]
        toks = np.zeros((bb, bucket), np.int32)
        lens = np.zeros((bb,), np.int32)
        table = np.zeros((bb, self.kv.max_pages_per_seq), np.int32)
        ids = np.full((bb,), self.kv.max_slots, np.int32)
        for i, (p, s) in enumerate(zip(prompts, slots)):
            toks[i, :len(p)], lens[i], ids[i] = p, len(p), s
            table[i] = self.kv._table[s]
        hidden, kp, st, moe = jax.jit(
            lambda *a: self.family.forward_prefill_into_pages(
                self.spec, self.params, *a))(
            jnp.asarray(toks), jnp.asarray(lens), self.kv.k_pages,
            self.kv.state, jnp.asarray(table), jnp.asarray(ids))
        self.kv.swap(kp, st)
        self.moe += np.asarray(moe)
        logits = unembed(self.spec, self.params, hidden)
        return slots, [np.asarray(logits[i, :len(p)])
                       for i, p in enumerate(prompts)]

    def decode(self, feeds, lengths, n_steps=4):
        """``feeds[slot]`` = the tokens to feed next; returns per slot the
        logits after each fed token."""
        b = self.kv.max_slots
        out = {s: [] for s in feeds}
        step = jax.jit(lambda kp, table, tok, cur, start, *a:
                       self.family.forward_decode_step(
            self.spec, self.params, tok, cur, start,
            self.family.decode_context(kp, table, self.attn_impl), *a))
        pos = dict(lengths)
        fed = {s: 0 for s in feeds}
        while any(fed[s] < len(feeds[s]) for s in feeds):
            for s in feeds:
                if fed[s] < len(feeds[s]):
                    self.kv.ensure_capacity(s, pos[s] + n_steps)
            start = np.zeros((b,), np.int32)
            for s in feeds:
                start[s] = pos[s]
            side = jnp.zeros((self.spec.paged_layers, b, n_steps,
                              self.spec.cache_row_width), self.kv.dtype)
            state = self.kv.state
            cur = start.copy()
            for _ in range(n_steps):
                tok = np.zeros((b,), np.int32)
                act = np.zeros((b,), bool)
                for s in feeds:
                    if fed[s] < len(feeds[s]):
                        tok[s], act[s] = feeds[s][fed[s]], True
                hidden, side, state, moe = step(
                    self.kv.k_pages, self.kv.page_table,
                    jnp.asarray(tok), jnp.asarray(cur), jnp.asarray(start),
                    side, state, jnp.asarray(act))
                if self.state_dtype is not None:
                    state = dict(state, S=state["S"].astype(
                        self.state_dtype).astype(jnp.float32))
                self.moe += np.asarray(moe)[:3]   # + the rows the attention read
                logits = np.asarray(unembed(self.spec, self.params, hidden))
                for s in feeds:
                    if act[s]:
                        out[s].append(logits[s])
                        fed[s] += 1
                        cur[s] += 1
            kp = self.family.write_rows_into_pages(
                self.kv.k_pages, side, self.kv.page_table,
                jnp.asarray(cur - start), jnp.asarray(start))
            self.kv.swap(kp, state)
            pos = {s: int(cur[s]) for s in feeds}
        return out, pos


def served_logits(spec, params, seqs, n_prompt, bucket=48, **kw):
    """Full-position logits of each sequence: its first ``n_prompt[i]``
    tokens prefilled together at a padded bucket, the rest decoded."""
    sv = Served(spec, params, **kw)
    prompts = [s[:n] for s, n in zip(seqs, n_prompt)]
    slots, pre = sv.prefill(prompts, bucket)
    dec, _ = sv.decode({sl: s[n:] for sl, s, n in zip(slots, seqs, n_prompt)},
                       {sl: n for sl, n in zip(slots, n_prompt)})
    return [np.concatenate([p, np.stack(dec[sl])]) if len(dec[sl]) else p
            for sl, p in zip(slots, pre)], sv


def sequences(seed=0, lens=(31, 47, 9)):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, CFG["vocab_size"], n)]
            for n in lens]


def max_diff(got, cfg, params, seqs):
    worst, scale = 0.0, 0.0
    for lg, seq in zip(got, seqs):
        ref = np.asarray(REF.logits(cfg, params, jnp.asarray(seq)))
        worst = max(worst, float(np.abs(lg - ref).max()))
        scale = max(scale, float(np.abs(ref).max()))
    return worst, scale


# ---------------------------------------------- served path vs the reference


PROMPTS = (20, 37, 5)


@pytest.fixture(scope="module")
def float32_run(served_f32):
    """Three rows of unequal length and a pad row prefilled at a padded
    bucket, then decoded through pages and state: once, for the tests that
    hold it against the reference and against altered references."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        got, sv = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                                PROMPTS)
    return seqs, got, sv


def test_served_float32_logits_are_the_references(served_f32, float32_run):
    seqs, got, sv = float32_run
    with jax.default_matmul_precision("highest"):
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert worst < F32_TOL and scale > 0.3, (worst, scale)
    assert sv.moe[1] > 0 and 0 < sv.moe[0] < sv.moe[1]


def test_prefill_into_pages_through_the_kernel_is_the_reference(monkeypatch,
                                                                served_f32):
    """The one MLA layer's prefill with the interpreted kernel forced
    (blocks of 16 in the bucket of 48, the plain softmax scale, the output
    gate after it) against the float32 reference, at the XLA body's limit."""
    from distributed_inference_engine_tpu.ops import mla

    monkeypatch.setattr(mla, "Q_BLOCK", 16)
    monkeypatch.setattr(mla, "K_BLOCK", 16)
    monkeypatch.setattr(mla, "prefill_impl", lambda t: "flash_interpret")
    seqs = [s[:n] for s, n in zip(sequences(), PROMPTS)]
    with jax.default_matmul_precision("highest"):
        _, got = Served(tiny_spec(dtype="float32"), served_f32).prefill(
            seqs, 48)
        worst, scale = max_diff(got, CFG, served_f32, seqs)
    assert worst < F32_TOL and scale > 0.3, (worst, scale)


def test_served_bfloat16_logits_are_near_the_references(served_bf16):
    seqs = sequences(1)
    got, _ = served_logits(tiny_spec(), served_bf16, seqs, (20, 37, 5))
    worst, scale = max_diff(got, CFG, served_bf16, seqs)
    assert worst < BF16_TOL * scale, (worst, scale)


def test_a_reused_slot_starts_from_zero_state(served_f32):
    """Free a slot after a long sequence, serve a fresh one in it: the
    state was zeroed, the pages re-issued, the logits the reference's."""
    spec = tiny_spec(dtype="float32")
    first, second = sequences(2, (40, 28))
    with jax.default_matmul_precision("highest"):
        sv = Served(spec, served_f32, slots=1)
        (slot,), _ = sv.prefill([first[:30]], 48)
        sv.decode({slot: first[30:]}, {slot: 30})
        assert float(jnp.abs(sv.kv.state["S"][:, slot]).max()) > 0
        sv.kv.free_slot(slot)
        assert all(float(jnp.abs(a[:, slot]).max()) == 0
                   for a in sv.kv.state.values())
        (slot2,), pre = sv.prefill([second[:17]], 48)
        assert slot2 == slot
        dec, _ = sv.decode({slot2: second[17:]}, {slot2: 17})
        got = np.concatenate([pre[0], np.stack(dec[slot2])])
        worst, _ = max_diff([got], CFG, served_f32, [second])
    assert worst < F32_TOL, worst


def mutated(params, fn):
    out = dict(params, layers=[dict(b) for b in params["layers"]])
    for blk in out["layers"]:
        fn(blk)
    return out


def _no_shared(blk):
    if "ws_down" in blk:
        blk["ws_down"] = jnp.zeros_like(blk["ws_down"])


def _no_bias(blk):
    if "router_bias" in blk:
        blk["router_bias"] = jnp.zeros_like(blk["router_bias"])


def _no_kda_gate(blk):
    if "w_g" in blk:
        blk["w_g"] = jnp.zeros_like(blk["w_g"])


def _no_mla_gate(blk):
    if "w_gate" in blk:
        blk["w_gate"] = jnp.zeros_like(blk["w_gate"])


@pytest.mark.parametrize("what", ["scaling_factor", "shared_expert",
                                  "expert_bias", "kda_gate", "mla_gate"])
def test_a_dropped_term_fails(served_f32, float32_run, what):
    """The tolerance is tight enough to see each: the same served logits
    against a reference whose weights or configuration lack one term."""
    seqs, got, _ = float32_run
    cfg, ref_params = CFG, served_f32
    if what == "scaling_factor":
        cfg = dict(CFG, routed_scaling_factor=1.0)
    else:
        ref_params = mutated(served_f32, {
            "shared_expert": _no_shared, "expert_bias": _no_bias,
            "kda_gate": _no_kda_gate, "mla_gate": _no_mla_gate}[what])
    with jax.default_matmul_precision("highest"):
        worst, _ = max_diff(got, cfg, ref_params, seqs)
    assert worst > 5 * F32_TOL, (what, worst)


def test_a_bfloat16_state_fails(served_f32):
    """The served side with S rounded to bfloat16 after every decode step."""
    seqs = sequences()
    with jax.default_matmul_precision("highest"):
        got, _ = served_logits(tiny_spec(dtype="float32"), served_f32, seqs,
                               PROMPTS, state_dtype=jnp.bfloat16)
        worst, _ = max_diff(got, CFG, served_f32, seqs)
    assert worst > 5 * F32_TOL, worst


def test_the_reference_in_bfloat16_is_the_control_not_the_reference(
        served_bf16):
    """``logits(..., dtype=bfloat16)`` (what ``perfbench/tools/control.py``
    reads beside the served chains) computes activations, KDA state and
    router below what the configuration states: it lands further from the
    float32 logits than the float32 tolerance by far, and the float32 call
    is unchanged by the argument's existence."""
    seq = jnp.asarray(sequences(2)[1])
    ref = np.asarray(REF.logits(CFG, served_bf16, seq))
    low = REF.logits(CFG, served_bf16, seq, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16 and ref.dtype == np.float32
    worst = float(np.abs(np.asarray(low, np.float32) - ref).max())
    assert 5 * F32_TOL < worst < BF16_TOL * float(np.abs(ref).max()) * 2


# ------------------------------------------------------------------- KDA


def kda_inputs(t, lower_bound=False, b=2, h=3, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5
    k = kda.l2_normalize(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = (jnp.full((b, t, h, d), -5.0 + 1e-3) if lower_bound else
         -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (b, t, h, d))))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("t,lower_bound", [(7, False), (64, False),
                                           (100, False), (131, True),
                                           (200, True), (193, False)])
def test_chunked_kda_is_the_token_scan(t, lower_bound):
    """Lengths that are no multiple of the chunk, and g at its lower bound
    (a 64-token chunk then spans e^-320: no factor may be formed alone)."""
    args = kda_inputs(t, lower_bound)
    o1, s1 = kda.kda_scan(*args)
    o2, s2 = jax.jit(kda.kda_chunked)(*args)
    assert bool(jnp.isfinite(o2).all()) and bool(jnp.isfinite(s2).all())
    assert float(jnp.abs(o1 - o2).max()) < 5e-6
    assert float(jnp.abs(s1 - s2).max()) < 5e-5


def test_kda_scan_is_the_references_layer(served_f32):
    """The oracle itself against the reference's KDA layer (projections,
    convolution, normalisation, gates included), and pad rows move nothing:
    the state and tail at a row's TRUE end equal those of the unpadded row."""
    spec = tiny_spec(dtype="float32")
    blk = served_f32["layers"][0]
    x = jax.random.normal(jax.random.key(3), (1, 23, spec.d_model)) * 0.3
    from distributed_inference_engine_tpu.ops.norms import rms_norm

    with jax.default_matmul_precision("highest"):
        want = REF.kda(CFG, blk, rms_norm(x[0], blk["ln1_scale"],
                                          spec.norm_eps))
        got, S, tail = ling.kda_layer_prefill(spec, blk, x,
                                              jnp.asarray([23]))
        xp = jnp.pad(x, ((0, 0), (0, 25), (0, 0)))
        got_p, S_p, tail_p = ling.kda_layer_prefill(spec, blk, xp,
                                                    jnp.asarray([23]))
    assert float(jnp.abs(got[0] - want).max()) < 1e-5
    assert float(jnp.abs(got_p[0, :23] - want).max()) < 1e-5
    assert float(jnp.abs(S - S_p).max()) < 1e-6
    assert bool((tail == tail_p).all())


# ------------------------------------------------------------------- MLA


@pytest.mark.parametrize("impl", ["xla", "pallas-decode_interpret"])
def test_absorbed_mla_decode_is_the_expanded_reference(served_f32, impl):
    """One query against cached rows (part frozen pages, part side window)
    = the reference's expanded attention at that position: over a layer's
    gathered pages (XLA) and read in place by the interpreted kernel."""
    spec = tiny_spec(dtype="float32")
    blk = served_f32["layers"][2]
    t = 21
    x = jax.random.normal(jax.random.key(4), (1, t, spec.d_model)) * 0.3
    from distributed_inference_engine_tpu.ops.norms import rms_norm

    with jax.default_matmul_precision("highest"):
        want = REF.mla(CFG, blk, rms_norm(x[0], blk["ln1_scale"],
                                          spec.norm_eps))
        pos = jnp.arange(t)[None]
        got, rows = ling.mla_layer_prefill(spec, blk, x, pos,
                                           jnp.asarray([t]))
        assert float(jnp.abs(got[0] - want).max()) < 1e-5
        # the last token again, as a decode step: 17 rows frozen in pages
        # 3 and 1 of the second of two paged layers, 3 in the side window,
        # its own written at side index 3
        w = rows.shape[-1]
        assert w == spec.cache_row_width == 128
        pool = jnp.zeros((2, 4, 16, w)).at[1, 3].set(rows[0, :16]).at[
            1, 1, 0].set(rows[0, 16])
        ctx = ling.decode_context(pool, jnp.asarray([[3, 1]]), impl)
        side = jnp.zeros((1, 8, w)).at[:, :3].set(rows[:, 17:20])
        out, side, read = ling.mla_layer_step(
            spec, blk, x[:, -1], jnp.asarray([t - 1]), ctx, 1,
            jnp.asarray([17]), side, jnp.asarray([3]), jnp.asarray([True]))
    assert float(jnp.abs(out[0] - want[-1]).max()) < 1e-5
    assert float(jnp.abs(side[0, 3] - rows[0, -1]).max()) < 1e-6
    assert int(read) == 2 * 16 + 8


def test_rope_rotates_interleaved_pairs():
    x = jnp.zeros((1, 1, 1, 8)).at[..., 2].set(1.0)
    y = mla.rope_interleaved(x, jnp.asarray([[3]]), 10000.0)
    ang = 3.0 * 10000.0 ** (-2 / 8)
    assert np.allclose(np.asarray(y[0, 0, 0]),
                       [0, 0, np.cos(ang), np.sin(ang), 0, 0, 0, 0],
                       atol=1e-6)


# ---------------------------------------------------------------- routing


def router_spec(**kw):
    return tiny_spec(**kw)


def route(spec, scores, bias=None):
    """Routing on given sigmoid pre-activations: x = one-hot rows, W_r =
    the logits themselves."""
    n, e = scores.shape
    w = jnp.asarray(scores, jnp.float32)
    b = jnp.zeros((e,)) if bias is None else jnp.asarray(bias, jnp.float32)
    return moe_routed.route(spec, jnp.eye(n), w, b)


def test_router_group_limit_bias_and_ties():
    spec = router_spec()                 # 16 experts, 4 groups, 2 kept, k=4
    logits = np.full((1, 16), -4.0, np.float32)
    # group 3 holds the single best expert, but groups 0 and 1 have the two
    # best top-2 SUMS: the choice stays inside groups 0 and 1
    logits[0, 12] = 3.0
    logits[0, [0, 1, 4, 5]] = [2.0, 1.9, 1.8, 1.7]
    idx, gates = route(spec, logits)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 4, 5]
    s = 1 / (1 + np.exp(-logits[0, np.asarray(idx[0])]))
    assert np.allclose(np.asarray(gates[0]), s / s.sum() * 2.5, atol=1e-6)
    # the bias moves the CHOICE, never the gate values
    bias = np.zeros(16, np.float32)
    bias[[8, 9]] = 5.0
    idx_b, gates_b = route(spec, logits, bias)
    chosen = np.asarray(idx_b[0]).tolist()
    assert 8 in chosen and 9 in chosen
    s = 1 / (1 + np.exp(-logits[0, chosen]))
    assert np.allclose(np.asarray(gates_b[0]), s / s.sum() * 2.5, atol=1e-6)
    # ties: all equal -> the lowest groups, the lowest experts in them, as
    # the reference's own top_k breaks them
    idx_t, gates_t = route(spec, np.zeros((1, 16), np.float32))
    assert sorted(np.asarray(idx_t[0]).tolist()) == [0, 1, 2, 3]
    assert np.allclose(np.asarray(gates_t[0]), 2.5 / 4)
    ref_g = REF.router(CFG, {"w_router": jnp.zeros((1, 16)),
                             "router_bias": jnp.zeros((16,))},
                       jnp.ones((1, 1)))
    assert np.flatnonzero(np.asarray(ref_g[0])).tolist() == [0, 1, 2, 3]


def moe_layer_params(spec, key, held):
    blk = ling._init_layer(spec.replace(experts_held=held, dtype="float32"),
                           "kda", "moe", 1, key)
    return {k: blk[k] for k in ("w_router", "router_bias", "w_gate_up",
                                "w_down", "ws_gate_up", "ws_down")}


@pytest.mark.parametrize("impl", ["xla", "gmm_interpret"])
def test_grouped_product_is_the_per_expert_loop(impl):
    """Sorted rows times their own expert, with experts that got no row,
    rows that landed elsewhere and rows that are not live."""
    spec = tiny_spec(dtype="float32", experts_held=(4, 8))
    blk = moe_layer_params(spec, jax.random.key(5), (4, 8))
    x = jax.random.normal(jax.random.key(6), (11, spec.d_model))
    valid = jnp.arange(11) != 4
    with jax.default_matmul_precision("highest"):
        out, counters = jax.jit(lambda x: moe_routed.moe_block(
            spec, blk, x, valid, impl))(x)
        idx, gates = moe_routed.route(spec, x, blk["w_router"],
                                      blk["router_bias"])
        want = np.array(REF.swiglu(x, blk["ws_gate_up"], blk["ws_down"]))
        held_rows, touched = 0, set()
        for n in range(11):
            for j in range(spec.experts_per_token):
                e = int(idx[n, j]) - 4
                if bool(valid[n]) and 0 <= e < 8:
                    held_rows += 1
                    touched.add(e)
                    want[n] += float(gates[n, j]) * np.asarray(REF.swiglu(
                        x[n], blk["w_gate_up"][e], blk["w_down"][e]))
    live = np.asarray(valid)
    assert np.abs(np.asarray(out)[live] - want[live]).max() < 1e-5
    assert len(touched) < 8                       # some expert got no row
    assert counters.tolist() == [held_rows, 10 * 4, len(touched)]


def test_the_shares_add_up_to_the_uncut_layer():
    """The four chips' routed parts plus the shared expert ONCE = what the
    reference gives for the whole layer with all 16 experts held."""
    key = jax.random.key(8)
    whole_spec = tiny_spec(dtype="float32", experts_held=(0, 16))
    whole = moe_layer_params(whole_spec, key, (0, 16))
    x = jax.random.normal(jax.random.key(9), (13, whole_spec.d_model))
    valid = jnp.ones((13,), bool)
    with jax.default_matmul_precision("highest"):
        want = REF.experts(dict(CFG, experts_held=[0, 16]), whole, x)
        shared = REF.swiglu(x, whole["ws_gate_up"], whole["ws_down"])
        total = jnp.zeros_like(want)
        held_sum = 0
        for first in (0, 4, 8, 12):
            spec = tiny_spec(dtype="float32", experts_held=(first, 4))
            part = dict(whole,
                        w_gate_up=whole["w_gate_up"][first:first + 4],
                        w_down=whole["w_down"][first:first + 4])
            out, counters = moe_routed.moe_block(spec, part, x, valid, "xla")
            total = total + (out - shared)
            held_sum += int(counters[0])
            # and each share is what the reference computes for that share
            ref_part = REF.experts(dict(CFG, experts_held=[first, 4]), part, x)
            assert float(jnp.abs(out - ref_part).max()) < 1e-5
    assert float(jnp.abs(total + shared - want).max()) < 1e-5
    assert held_sum == 13 * whole_spec.experts_per_token


def test_padded_rows_and_dead_rows_do_not_move_the_state(served_f32):
    """A row not live in a decode step keeps S and its conv tail bit for
    bit; a pad row of a prefill writes no slot."""
    spec = tiny_spec(dtype="float32")
    sv = Served(spec, served_f32)
    seqs = sequences(4, (25, 18))
    slots, _ = sv.prefill([s[:12] for s in seqs], 32)
    before = jax.tree.map(np.asarray, sv.kv.state)
    sv.decode({slots[0]: seqs[0][12:16]}, {slots[0]: 12})
    after = jax.tree.map(np.asarray, sv.kv.state)
    for name in before:
        assert (before[name][:, slots[1]] == after[name][:, slots[1]]).all()
        assert (before[name][:, slots[0]] != after[name][:, slots[0]]).any()
        untouched = [s for s in range(4) if s not in slots]
        assert not after[name][:, untouched].any()
