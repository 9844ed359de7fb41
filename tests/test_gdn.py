"""The delta rule both gates share (``ops/kda.py``): one token (``kda_step``;
``kda_step_inplace``, the same step over the engine's whole state array, its
kernel through the interpreter here),
token by token (``kda_scan``, the oracle) and the chunked WY form
(``kda_chunked``, the served prefill) give the same outputs and the same
state for Gated DeltaNet's shapes (one decay a head, ``dk != dv``, beta up to
2) and, through the SAME functions, for KDA's (a decay per key channel).
Float32, on the CPU: the three forms differ by rounding order alone; 2e-5
bounds outputs of magnitude ~1 (seen: 6e-7 outputs, 1.3e-5 the state)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.ops import kda

TOL = 2e-5


def draw(seed, b, t, h, dk, dv, gate, beta_max=1.99):
    ks = jax.random.split(jax.random.key(seed), 7)
    q = kda.l2_normalize(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = kda.l2_normalize(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    beta = beta_max * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (b, t, h)))
    if gate == "head":
        g = kda.gdn_gate(jax.random.normal(ks[4], (b, t, h)),
                         jnp.log(jnp.linspace(0.01, 4.0, h)),
                         jax.random.normal(ks[5], (h,)))
    else:
        g = kda.kda_gate(jax.random.normal(ks[4], (b, t, h * dk)),
                         jnp.zeros((h,)), jax.random.normal(ks[5], (h * dk,)),
                         -5.0)
    return q, k, v, g, beta


# b, t, h, dk, dv, gate: Gated DeltaNet's shapes (keys half as wide as values,
# prompts that are no multiple of the chunk of 64 nor of the sub-block of 16),
# KDA's own (square state, per-channel gate), and each gate on the other's
@pytest.mark.parametrize("b,t,h,dk,dv,gate", [
    (2, 77, 3, 24, 48, "head"), (1, 64, 2, 24, 48, "head"),
    (2, 130, 2, 16, 32, "head"), (1, 5, 2, 24, 48, "head"),
    (2, 77, 3, 16, 16, "channel"), (1, 130, 2, 16, 16, "channel"),
    (2, 50, 2, 24, 48, "channel"), (2, 50, 2, 16, 16, "head")])
def test_chunked_is_the_scan(b, t, h, dk, dv, gate):
    q, k, v, g, beta = draw(t, b, t, h, dk, dv, gate)
    assert g.shape == (b, t, h, 1 if gate == "head" else dk)
    assert float(beta.max()) > 1.9 and float(jnp.exp(g).min()) < 0.5
    o1, S1 = kda.kda_scan(q, k, v, g, beta)
    o2, S2 = kda.kda_chunked(q, k, v, g, beta)
    assert o1.shape == (b, t, h, dv) and S1.shape == (b, h, dk, dv)
    assert float(jnp.abs(o1 - o2).max()) < TOL
    assert float(jnp.abs(S1 - S2).max()) < 4 * TOL
    assert float(jnp.abs(o1).max()) > 0.1


def test_one_decay_a_head_needs_its_own_construction():
    """Why ``kda_chunked`` has two constructions of A and B: the per-channel
    one re-bases each 16-row sub-block and clamps at e^80, which holds under
    KDA's gate (no less than -5 a token) and NOT under Gated DeltaNet's,
    which has no lower bound (here down to -14 a token). The same gate
    broadcast over the key channels goes through the per-channel
    construction and comes out wrong; as one decay a head it is the scan."""
    q, k, v, g, beta = draw(130, 2, 130, 2, 16, 32, "head")
    assert float(g.min()) < -10
    want, _ = kda.kda_scan(q, k, v, g, beta)
    got, _ = kda.kda_chunked(q, k, v, g, beta)
    wide, _ = kda.kda_chunked(q, k, v, jnp.broadcast_to(g, k.shape), beta)
    assert float(jnp.abs(want - got).max()) < TOL
    assert float(jnp.abs(want - wide).max()) > 0.05


@pytest.mark.parametrize("gate,dk,dv", [("head", 24, 48), ("channel", 16, 16)])
def test_steps_from_a_prefilled_state_are_the_scan(gate, dk, dv):
    """Prefill 37 tokens chunked, then 20 single steps from its state: the
    outputs and the final state are the scan's over all 57."""
    q, k, v, g, beta = draw(3, 2, 57, 3, dk, dv, gate)
    want_o, want_S = kda.kda_scan(q, k, v, g, beta)
    _, S = kda.kda_chunked(*(a[:, :37] for a in (q, k, v, g, beta)))
    outs = []
    for i in range(37, 57):
        o, S = kda.kda_step(S, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        outs.append(o)
    assert float(jnp.abs(jnp.stack(outs, 1) - want_o[:, 37:]).max()) < TOL
    assert float(jnp.abs(S - want_S).max()) < 4 * TOL


def test_states_are_packed_to_whole_lane_tiles():
    """``lane_pack``: two heads of 192 value lanes a row (384 = 3 tiles),
    one of 128, four of 32; 1 where no count of heads fills a tile; and
    ``pack_states`` puts head ``m i + j`` at lanes ``j dv .. (j + 1) dv`` of
    row i, which ``unpack_states`` undoes."""
    assert [kda.lane_pack(h, dv) for h, dv in
            ((30, 192), (32, 128), (4, 32), (3, 48), (2, 48))] == [
                2, 1, 4, 1, 1]
    S = jax.random.normal(jax.random.key(0), (3, 2, 6, 8, 192))
    P = kda.pack_states(S, 2)
    assert P.shape == (3, 2, 3, 8, 384)
    assert np.array_equal(np.asarray(P[:, :, 1, :, 192:]),
                          np.asarray(S[:, :, 3]))
    assert np.array_equal(np.asarray(kda.unpack_states(P, 2)), np.asarray(S))


# the cells' state shapes: Gated DeltaNet's 96 x 192 under one decay a head
# (two heads a row of 384 lanes, as the engine keeps them), KDA's 128 x 128
# under one a key channel; 3 layers of 4 slots of 4 heads, two heads a
# block, so every case crosses a slot's and a block's edge
@pytest.mark.parametrize("index", ["static", "scanned"])
@pytest.mark.parametrize("live", [(True,) * 4, (False, True, False, True),
                                  (True, False, False, False), (False,) * 4],
                         ids=["all", "some", "first", "none"])
@pytest.mark.parametrize("gate,dk,dv", [("head", 96, 192),
                                        ("channel", 128, 128)])
def test_the_inplace_kernel_is_the_step_on_the_live_slots(
        monkeypatch, gate, dk, dv, live, index):
    """``kda_step_inplace`` through the interpreter against ``kda_step``: the
    live slots' ``o`` and states within float32 rounding; the dead slots'
    states and every OTHER layer's bit-equal; the layer a Python int or an
    index traced inside a ``lax.scan`` (as the Gated-DeltaNet family's scan
    over periods hands it)."""
    nl, b, h, layer = 3, 4, 4, 1
    m = kda.lane_pack(h, dv)
    monkeypatch.setattr(kda, "STEP_BLOCK_BYTES", 2 * 96 * 256 * 4)
    assert kda._rows_per_block(h // m, dk, m * dv) * m == 2
    q, k, v, g, beta = (a[:, 0] for a in draw(7, b, 1, h, dk, dv, gate))
    S_plain = jax.random.normal(jax.random.key(1), (nl, b, h, dk, dv))
    S_all = kda.pack_states(S_plain, m)
    active = jnp.array(live)

    def step(S_all, at):
        return kda.kda_step_inplace(S_all, at, q, k, v, g, beta, active,
                                    impl="inplace_interpret")

    if index == "static":
        o, got = jax.jit(lambda S: step(S, layer))(S_all)
    else:
        def body(S, at):
            o, S = step(S, at)
            return S, o

        got, o = jax.jit(lambda S: jax.lax.scan(
            body, S, jnp.array([layer])))(S_all)
        o = o[0]
    want_o, want_S = kda.kda_step(S_plain[layer], q, k, v, g, beta)
    on = np.asarray(live)
    assert got.shape == S_all.shape
    S_all = np.asarray(S_all)
    plain = np.asarray(kda.unpack_states(got, m))
    if on.any():
        assert np.abs(np.asarray(o)[on] - np.asarray(want_o)[on]).max() < 1e-6
        assert np.abs(plain[layer][on] - np.asarray(want_S)[on]).max() < 1e-6
        assert np.abs(plain[layer][on] - S_plain[layer][on]).max() > 0.1
    got = np.asarray(got)
    assert np.array_equal(got[layer][~on], S_all[layer][~on])
    assert np.array_equal(got[[0, 2]], S_all[[0, 2]])
    # the XLA body, which the CPU serves with, keeps the same contract
    o_x, got_x = kda.kda_step_inplace(S_all, layer, q, k, v, g, beta, active,
                                      impl="xla")
    assert np.array_equal(np.asarray(got_x)[layer][~on], S_all[layer][~on])
    assert np.abs(np.asarray(got_x) - got).max() < 1e-6


@pytest.mark.parametrize("gate,dk,dv", [("head", 24, 64), ("channel", 16, 16)])
def test_sixteen_inplace_steps_in_a_carry_are_the_scan(gate, dk, dv):
    """A decode chunk's shape: the state array rides a ``lax.scan`` carry
    through 16 steps of the interpreted kernel on layer 2 of 3, slot 1 dead
    throughout; live slots' outputs and final states are ``kda_scan``'s."""
    nl, b, h, t, layer = 3, 3, 2, 16, 2
    q, k, v, g, beta = draw(11, b, t, h, dk, dv, gate)
    want_o, want_S = kda.kda_scan(q, k, v, g, beta)
    active = jnp.array([True, False, True])
    m = kda.lane_pack(h, dv)
    S0 = jnp.zeros((nl, b, h // m, dk, m * dv)).at[:, 1].set(3.0)

    def body(S_all, xs):
        o, S_all = kda.kda_step_inplace(S_all, layer, *xs, active,
                                        impl="inplace_interpret")
        return S_all, o

    S_all, o = jax.jit(lambda S: jax.lax.scan(
        body, S, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))))(
            S0)
    on = np.asarray(active)
    assert float(jnp.abs(jnp.moveaxis(o, 0, 1) - want_o)[on].max()) < TOL
    assert float(jnp.abs(kda.unpack_states(S_all[layer], m)
                         - want_S)[on].max()) < 4 * TOL
    assert np.array_equal(np.asarray(S_all[:, 1]), np.asarray(S0[:, 1]))
    assert not np.asarray(S_all[:layer, on]).any()


def test_rows_shorter_than_the_bucket_keep_their_state():
    """``seq_lens`` shorter than the bucket: pad positions carry beta = 0 and
    g = 0 (as the served prefill masks them) and the state at the end is the
    state at each row's TRUE end; a chunked run continues from ``S0``."""
    q, k, v, g, beta = draw(5, 2, 96, 2, 24, 48, "head")
    lens = jnp.array([41, 96])
    live = jnp.arange(96)[None, :] < lens[:, None]
    beta_m = jnp.where(live[..., None], beta, 0.0)
    g_m = jnp.where(live[..., None, None], g, 0.0)
    _, S = kda.kda_chunked(q, k, v, g_m, beta_m)
    _, S_short = kda.kda_scan(*(a[:1, :41] for a in (q, k, v, g, beta)))
    _, S_full = kda.kda_scan(*(a[1:] for a in (q, k, v, g, beta)))
    assert float(jnp.abs(S[0] - S_short[0]).max()) < 4 * TOL
    assert float(jnp.abs(S[1] - S_full[0]).max()) < 4 * TOL
    o_a, S_a = kda.kda_chunked(*(a[:, :50] for a in (q, k, v, g, beta)))
    o_b, S_b = kda.kda_chunked(*(a[:, 50:] for a in (q, k, v, g, beta)),
                               S0=S_a)
    want_o, want_S = kda.kda_scan(q, k, v, g, beta)
    assert float(jnp.abs(jnp.concatenate([o_a, o_b], 1) - want_o).max()) < TOL
    assert float(jnp.abs(S_b - want_S).max()) < 4 * TOL


def test_beta_two_reflects_the_state_along_k():
    """beta = 2, no decay: the transition along k has eigenvalue -1 (the
    published ``linear_allow_neg_eigval``): k^T S flips sign, before the
    write of v."""
    S = jax.random.normal(jax.random.key(0), (1, 1, 8, 16))
    k = kda.l2_normalize(jax.random.normal(jax.random.key(1), (1, 1, 8)))
    zero = jnp.zeros((1, 1, 16))
    _, S2 = kda.kda_step(S, k, k, zero, jnp.zeros((1, 1, 1)),
                         jnp.full((1, 1), 2.0))
    before = jnp.einsum("bhk,bhkv->bhv", k, S)
    after = jnp.einsum("bhk,bhkv->bhv", k, S2)
    assert float(jnp.abs(after + before).max()) < 1e-5


def test_the_gates_lie_where_their_formulas_say():
    a = jnp.linspace(-6, 6, 12).reshape(1, 4, 3)
    g = kda.gdn_gate(a, jnp.log(jnp.array([0.5, 1.0, 2.0])), jnp.zeros((3,)))
    assert g.shape == (1, 4, 3, 1) and float(g.max()) < 0
    np.testing.assert_allclose(
        np.asarray(g[0, :, 1, 0]),
        -np.log1p(np.exp(np.asarray(a[0, :, 1]))), rtol=1e-5)
    gk = kda.kda_gate(jnp.zeros((1, 2, 6)), jnp.zeros((3,)), jnp.zeros((6,)),
                      -5.0)
    assert gk.shape == (1, 2, 3, 2)
    np.testing.assert_allclose(np.asarray(gk), -2.5)


def test_conv_forms_agree_for_any_channel_count():
    """The three convolution helpers over 2 * H * dk + H * dv channels: the
    sequence form, one step against the tail, and the tail at a row's TRUE
    end."""
    c, taps, t = 2 * 2 * 24 + 2 * 48, 4, 21
    x = jax.random.normal(jax.random.key(2), (2, t, c))
    w = jax.random.normal(jax.random.key(3), (taps, c))
    y = kda.causal_conv(x, w)
    tail = kda.conv_tail(x[:, :t - 1], jnp.array([t - 1, 2]), taps)
    assert tail.shape == (2, taps - 1, c)
    y_last, new_tail = kda.conv_step(tail, x[:, t - 1], w)
    assert float(jnp.abs(y_last[0] - y[0, t - 1]).max()) < 1e-5
    assert float(jnp.abs(new_tail[0] - x[0, t - 3:]).max()) == 0
    # the short row's tail: zeros where it has no history
    assert float(jnp.abs(tail[1, 0]).max()) == 0
    assert float(jnp.abs(tail[1, 1:] - x[1, :2]).max()) == 0
