"""The delta rule both gates share (``ops/kda.py``): one token (``kda_step``),
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
