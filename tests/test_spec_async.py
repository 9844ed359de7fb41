"""Bubble-scheduled async speculation tests (engine/spec_async.py +
engine/spec_accept.py + the continuous engine's verify chunk — ISSUE 15).

Correctness bar, same as the r5 sync engine but stricter in scope:
speculation may only change LATENCY, never content. Greedy output with
the drafter on must be token-for-token the plain continuous engine's own
chain — for any draft quality (accept-all through reject-all), any
weight dtype, and any bubble-budget decision. The acceptance math itself
is pinned bit-for-bit against a frozen reimplementation of the r5
rejection-sampling rule so the shared module can never drift under
either consumer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_inference_engine_tpu.config import EngineConfig
from distributed_inference_engine_tpu.engine.continuous import (
    ContinuousEngine,
)
from distributed_inference_engine_tpu.engine.spec_accept import (
    rejection_accept,
)
from distributed_inference_engine_tpu.engine.spec_async import resolve_draft
from distributed_inference_engine_tpu.engine.speculative import (
    scale_top_blocks,
)
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models.base import (
    ModelSpec,
    init_params,
)
from distributed_inference_engine_tpu.obs.timeline import busy_gap_split

pytestmark = pytest.mark.spec

# n_kv_heads * head_dim must stay a multiple of 128 (paged-layout lane
# alignment); 2 heads x 64 = 128 is the smallest compliant shape.
SPEC = ModelSpec(vocab_size=128, d_model=128, n_layers=2, n_heads=2,
                 n_kv_heads=2, d_ff=128, max_seq_len=128, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(SPEC, jax.random.key(0))


def _cfg(spec_async, floor=0.0, k=4):
    return EngineConfig(max_slots=4, page_size=16, num_pages=64,
                        max_seq_len=96, decode_steps_per_call=6,
                        spec_async=spec_async, spec_draft_model="layers:1",
                        spec_max_draft=k, spec_bubble_floor_s=floor)


def _run(spec, params, cfg, *, temp=0.0, n_req=3, nt=20, seed=0,
         draft=None):
    """Submit ``n_req`` streamed requests, pump to completion; returns
    (streamed tokens per request, engine)."""
    kw = {}
    if draft is not None:
        kw = {"draft_spec": draft[0], "draft_params": draft[1]}
    eng = ContinuousEngine(spec, params, cfg, seed=seed, **kw)
    streamed = {i: [] for i in range(n_req)}
    for i in range(n_req):
        r = GenerationRequest(prompt=[7 + i, 11, 13], max_new_tokens=nt,
                              temperature=temp)
        eng.submit(r, on_tokens=(lambda t, i=i: streamed[i].extend(t)))
    for _ in range(400):
        if eng.step() == 0 and not eng.n_waiting:
            break
    return streamed, eng


# ---------------------------------------------------------------------------
# acceptance math: bit-parity against a frozen r5 reference
# ---------------------------------------------------------------------------


def _frozen_r5_accept(p, q, drafts, greedy, key_resid, key_bonus,
                      valid=None):
    """Independent numpy reimplementation of the r5 acceptance block
    (frozen at the refactor): loop form, same key usage and op order as
    the pre-refactor ``_round_core``. Any drift in the shared module
    shows up as a bit mismatch here."""
    b, k = drafts.shape
    u = np.asarray(jax.random.uniform(key_resid, drafts.shape))
    accept = np.zeros((b, k), bool)
    for i in range(b):
        for j in range(k):
            d = int(drafts[i, j])
            if greedy[i]:
                accept[i, j] = int(np.argmax(p[i, j])) == d
            else:
                accept[i, j] = u[i, j] * q[i, j, d] < p[i, j, d]
            if valid is not None and not valid[i, j]:
                accept[i, j] = False
    n_acc = np.zeros(b, np.int32)
    for i in range(b):
        while n_acc[i] < k and accept[i, n_acc[i]]:
            n_acc[i] += 1
    final_dist = np.zeros((b, p.shape[-1]))
    for i in range(b):
        if n_acc[i] == k:
            final_dist[i] = p[i, k]
        else:
            pos = min(int(n_acc[i]), k - 1)
            resid = np.maximum(p[i, pos] - q[i, pos], 0.0)
            if resid.sum() <= 1e-9:
                resid = p[i, pos]
            final_dist[i] = resid / resid.sum()
    f_samp = np.asarray(jax.random.categorical(
        key_bonus, jnp.log(jnp.maximum(jnp.asarray(final_dist), 1e-30)),
        axis=-1))
    final = np.where(greedy, final_dist.argmax(-1), f_samp)
    return n_acc, final.astype(np.int32), accept


@pytest.mark.parametrize("greedy_all", [True, False])
@pytest.mark.parametrize("masked", [False, True])
def test_rejection_accept_bit_parity_vs_frozen_r5(greedy_all, masked):
    b, k, v = 5, 4, 32
    rng = np.random.RandomState(7 + masked)
    p = rng.dirichlet(np.ones(v) * 0.3, size=(b, k + 1))
    q = rng.dirichlet(np.ones(v) * 0.3, size=(b, k))
    drafts = rng.randint(0, v, size=(b, k)).astype(np.int32)
    greedy = np.full(b, greedy_all)
    valid = (rng.rand(b, k) < 0.6) if masked else None
    kr, kb = jax.random.split(jax.random.key(3))
    n_ref, f_ref, a_ref = _frozen_r5_accept(p, q, drafts, greedy, kr, kb,
                                            valid)
    n, f, a = rejection_accept(
        jnp.asarray(p, jnp.float32), jnp.asarray(q, jnp.float32),
        jnp.asarray(drafts), jnp.asarray(greedy), kr, kb,
        valid=None if valid is None else jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(n), n_ref)
    np.testing.assert_array_equal(np.asarray(f), f_ref)
    np.testing.assert_array_equal(np.asarray(a), a_ref)


def test_plain_rows_reduce_to_plain_decode():
    """A verify row with zero draft columns (all-False mask + zero
    q_probs) must sample exactly the target distribution at position 0 —
    that is what lets plain rows ride the verify program unchanged."""
    b, k, v = 3, 4, 16
    rng = np.random.RandomState(11)
    p = rng.dirichlet(np.ones(v), size=(b, k + 1)).astype(np.float32)
    q = np.zeros((b, k, v), np.float32)
    drafts = np.zeros((b, k), np.int32)
    kr, kb = jax.random.split(jax.random.key(5))
    n, f, _ = rejection_accept(
        jnp.asarray(p), jnp.asarray(q), jnp.asarray(drafts),
        jnp.asarray(np.ones(b, bool)), kr, kb,
        valid=jnp.zeros((b, k), bool))
    assert np.asarray(n).tolist() == [0] * b
    np.testing.assert_array_equal(np.asarray(f), p[:, 0].argmax(-1))


# ---------------------------------------------------------------------------
# greedy chain identity across weight dtypes and drafter extremes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def base_off(params):
    streamed, _ = _run(SPEC, params, _cfg(False))
    return streamed


def test_greedy_exact_f32(params, base_off):
    streamed, eng = _run(SPEC, params, _cfg(True))
    assert streamed == base_off
    m = eng.get_metrics()
    assert m["spec_async_drafted_tokens"] > 0, "drafter never engaged"
    assert m["spec_async_verify_steps"] > 0, "verify path never ran"
    # compile-count guard: the verify program buckets only on the stop
    # mask — one fixed [B, k+1] window shape, at most two programs
    assert eng.timeline.to_chrome_trace()["metadata"]["dropped_events"] == 0
    verify_programs = {e["args"]["program"] for e in eng.timeline.events()
                       if e["name"] == "engine.verify.dispatch"}
    assert 0 < len(verify_programs) <= 2, verify_programs


def test_greedy_exact_int4(params):
    from distributed_inference_engine_tpu.ops.quant import quantize_params

    qparams = quantize_params(SPEC, params, bits=4)
    off, _ = _run(SPEC, qparams, _cfg(False))
    on, eng = _run(SPEC, qparams, _cfg(True))
    assert on == off
    assert eng.get_metrics()["spec_async_drafted_tokens"] > 0


def test_accept_all_extreme(params):
    """eps=0 scaled target + layers:1 draft: the drafter's forward IS the
    target's (top block contributes zero residual), so greedy acceptance
    hits the machinery ceiling — only budget-cut tails are lost."""
    sp = scale_top_blocks(SPEC, params, n_shared=1, eps=0.0)
    off, _ = _run(SPEC, sp, _cfg(False))
    on, eng = _run(SPEC, sp, _cfg(True))
    assert on == off
    m = eng.get_metrics()
    assert m["spec_async_accept_rate"] >= 0.9, m["spec_async_accept_rate"]


def test_reject_all_extreme(params, base_off):
    """An independently initialized draft agrees with the target
    near-never — acceptance collapses but output must not move."""
    d_spec = SPEC.replace(n_layers=1)
    d_params = init_params(d_spec, jax.random.key(99))
    streamed, eng = _run(SPEC, params, _cfg(True),
                         draft=(d_spec, d_params))
    assert streamed == base_off
    m = eng.get_metrics()
    assert m["spec_async_drafted_tokens"] > 0
    assert m["spec_async_accept_rate"] < 0.2, m["spec_async_accept_rate"]


def test_saturation_auto_idle(params, base_off):
    """A bubble floor the rig can never clear must idle the drafter
    completely (zero drafted tokens, zero verify dispatches) while output
    stays the plain chain — the <=2% saturation-goodput contract's
    mechanism."""
    streamed, eng = _run(SPEC, params, _cfg(True, floor=10.0))
    assert streamed == base_off
    m = eng.get_metrics()
    assert m["spec_async_drafted_tokens"] == 0
    assert m["spec_async_verify_steps"] == 0
    assert m["spec_async_auto_idles"] > 0


def test_same_seed_determinism(params):
    """Sampled decode with the drafter on: two same-seed runs must emit
    identical streams AND identical drafter ledgers (the fleet receipts
    contract, at engine scope)."""
    a, ea = _run(SPEC, params, _cfg(True), temp=0.8)
    b, eb = _run(SPEC, params, _cfg(True), temp=0.8)
    assert a == b
    ma, mb = ea.get_metrics(), eb.get_metrics()
    for key in ("spec_async_drafted_tokens", "spec_async_accepted_tokens",
                "spec_async_wasted_tokens", "spec_async_verify_steps"):
        assert ma[key] == mb[key], key


# ---------------------------------------------------------------------------
# scheduling contracts: hook ordering, mid-flight catch-up only
# ---------------------------------------------------------------------------


def test_spec_async_rejects_defer_sync(params):
    cfg = _cfg(True)
    cfg.defer_sync = True
    cfg.num_pages = 4 * (96 // 16)   # fully backed, isolates the spec gate
    with pytest.raises(ValueError, match="spec_async"):
        ContinuousEngine(SPEC, params, cfg, seed=0)


def test_resolve_draft_layer_clamp(params):
    d_spec, _ = resolve_draft(SPEC, params, "layers:9")
    assert d_spec.n_layers == SPEC.n_layers - 1
    with pytest.raises(ValueError):
        resolve_draft(SPEC.replace(n_layers=1),
                      init_params(SPEC.replace(n_layers=1),
                                  jax.random.key(0)), "layers:1")


def test_pump_overlap_hook_runs_poll_before_draft():
    """Ordering regression pin: inside the pump's overlap hook the stream
    ring drains BEFORE the drafter schedules — computed tokens beat
    predicted ones, and the poll commits state the draft catch-up reads."""
    from distributed_inference_engine_tpu.serving.pump import EnginePump

    calls = []

    class _Spec:
        def schedule(self):
            calls.append("draft")
            return 0

    class _Eng:
        config = EngineConfig()
        overlap_hook = None
        speculator = _Spec()

        def poll_stream(self):
            calls.append("poll")
            return 0

        def step(self):
            return 0

        def drain_finished(self):
            return []

    eng = _Eng()
    EnginePump(eng)
    assert eng.overlap_hook is not None
    eng.overlap_hook()
    assert calls == ["poll", "draft"]


def test_midflight_schedule_is_catchup_only(params):
    """Draft overrun can never delay the next dispatch because a
    mid-flight schedule() (called from the overlap hook while a chunk is
    in flight) only catches caches up — it must never create a pending
    proposal the verify path would have to wait on. Also checks the
    bubble split the budget reads stays well-formed."""
    eng = ContinuousEngine(SPEC, params, _cfg(True), seed=0)
    spec = eng.speculator
    seen = []
    orig = spec.schedule

    def wrapped():
        before = set(spec._pending)
        n = orig()
        seen.append((eng._inflight_chunks,
                     set(spec._pending) - before))
        return n

    spec.schedule = wrapped
    # stand in for the pump's overlap hook (no pump in this test): the
    # engine fires it right after dispatching each chunk, mid-flight
    eng.overlap_hook = wrapped
    streamed = []
    eng.submit(GenerationRequest(prompt=[3, 5, 7], max_new_tokens=24,
                                 temperature=0.0),
               on_tokens=streamed.extend)
    for _ in range(400):
        if eng.step() == 0 and not eng.n_waiting:
            break
    midflight = [s for s in seen if s[0] >= 1]
    assert midflight, "overlap hook never invoked the drafter"
    assert all(not new for _, new in midflight), \
        "mid-flight schedule() created a pending proposal"
    assert any(new for infl, new in seen if infl == 0), \
        "step-top schedule() never proposed"
    split = busy_gap_split(eng.timeline.events())
    assert split["n_events"] > 0 and split["busy_s"] > 0
    assert 0.0 <= split["bubble_frac"] <= 1.0
