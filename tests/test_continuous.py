"""Continuous-batching engine: greedy parity with the static engine,
mid-flight admission, page-pool pressure, and capacity finishes."""

import jax.numpy as jnp
import numpy as np

from distributed_inference_engine_tpu.config import EngineConfig
from distributed_inference_engine_tpu.engine.continuous import ContinuousEngine
from distributed_inference_engine_tpu.engine.engine import Engine
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models.base import ModelSpec

SPEC = ModelSpec(
    vocab_size=512, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=256, max_seq_len=256, dtype="float32",
)


def _cfg(**kw):
    base = dict(
        max_slots=4, max_seq_len=128, prefill_buckets=[16, 64],
        page_size=16, num_pages=32, decode_steps_per_call=4,
        attention_impl="xla", kv_dtype="float32",
    )
    base.update(kw)
    return EngineConfig(**base)


def _reqs(rs, n, prompt_len=10, max_new=12):
    return [
        GenerationRequest(
            prompt=rs.randint(1, SPEC.vocab_size, size=prompt_len).tolist(),
            max_new_tokens=max_new, temperature=0.0, request_id=f"r{i}",
        )
        for i in range(n)
    ]


def test_greedy_parity_with_static_engine():
    """Same params, same greedy prompts -> identical tokens from the
    continuous (paged) and static (contiguous) engines."""
    rs = np.random.RandomState(0)
    reqs = _reqs(rs, 3)
    static = Engine(SPEC, config=_cfg(), seed=0)
    cont = ContinuousEngine(SPEC, params=static.params, config=_cfg(), seed=0)
    out_s = static.generate([GenerationRequest(**{
        "prompt": r.prompt, "max_new_tokens": r.max_new_tokens,
        "temperature": 0.0, "request_id": r.request_id}) for r in reqs])
    out_c = cont.generate(reqs)
    for a, b in zip(out_s, out_c):
        assert a.request_id == b.request_id
        assert a.tokens == b.tokens, (a.tokens, b.tokens)
        assert b.finish_reason == "length"


def test_mid_flight_admission():
    """Requests submitted while others decode join without disturbing them."""
    rs = np.random.RandomState(1)
    cont = ContinuousEngine(SPEC, config=_cfg(max_slots=2), seed=0)
    first = _reqs(rs, 2, max_new=20)
    for r in first:
        cont.submit(r)
    cont.step()                      # both admitted + one chunk
    assert cont.n_live == 2
    late = GenerationRequest(prompt=[7, 8, 9], max_new_tokens=4,
                             temperature=0.0, request_id="late")
    cont.submit(late)
    assert cont.n_waiting == 1       # no free slot yet
    results = cont.run_until_idle()
    ids = {r.request_id for r in results}
    assert ids == {"r0", "r1", "late"}
    late_res = next(r for r in results if r.request_id == "late")
    assert len(late_res.tokens) == 4


def test_eos_stops_early_and_frees_slot():
    rs = np.random.RandomState(2)
    cont = ContinuousEngine(SPEC, config=_cfg(), seed=0)
    # run one greedy request to learn its 3rd token, then use it as eos
    probe = cont.generate(_reqs(rs, 1, max_new=8))[0]
    eos = probe.tokens[2]
    rs = np.random.RandomState(2)    # same prompt again
    req = _reqs(rs, 1, max_new=8)[0]
    req.eos_id = eos
    res = cont.generate([req])[0]
    assert res.finish_reason == "stop"
    assert res.tokens == probe.tokens[:3]
    assert cont.kv.get_stats()["live_slots"] == 0


def test_page_pool_pressure_shortens_but_completes():
    """A pool far too small for all requests at once still completes all of
    them (admission control queues, capacity finishes bound sequences)."""
    rs = np.random.RandomState(3)
    cfg = _cfg(max_slots=4, num_pages=6, page_size=16, max_seq_len=96)
    cont = ContinuousEngine(SPEC, config=cfg, seed=0)
    reqs = _reqs(rs, 6, prompt_len=20, max_new=30)
    results = cont.generate(reqs)
    assert len(results) == 6
    assert {r.request_id for r in results} == {f"r{i}" for i in range(6)}
    for r in results:
        assert len(r.tokens) >= 1
    stats = cont.get_metrics()
    assert stats["kv"]["pages_used"] == 0            # everything freed
    assert stats["admission_denied"] > 0             # pool actually pressured


def test_max_seq_len_capacity_finish():
    """A request that would decode past max_seq_len is finished with
    reason 'length' instead of corrupting pages (review finding)."""
    cfg = _cfg(max_slots=1, num_pages=32, page_size=16, max_seq_len=32)
    cont = ContinuousEngine(SPEC, config=cfg, seed=0)
    req = GenerationRequest(prompt=list(range(1, 29)), max_new_tokens=50,
                            temperature=0.0, request_id="long")
    res = cont.generate([req])[0]
    assert res.finish_reason == "length"
    # 28 prompt + n generated <= 32 total positions -> at most 4 generated
    assert 1 <= len(res.tokens) <= 5
    assert cont.get_metrics()["kv"]["pages_used"] == 0


def test_max_seq_len_finish_skips_pause_revive():
    """A slot that stops exactly at max_seq_len with budget left must be
    finished as "length" in the same harvest — NOT revived for one more
    dispatch that the next capacity loop retires anyway. The revive path
    exists for page-boundary pauses the pool can still grow past;
    max_seq_len it cannot, and the old behavior both inflated
    ``capacity_finishes`` and paid an extra active-flag dispatch pair."""
    cfg = _cfg(max_slots=1, num_pages=32, page_size=16, max_seq_len=32)
    cont = ContinuousEngine(SPEC, config=cfg, seed=0)
    req = GenerationRequest(prompt=list(range(1, 29)), max_new_tokens=50,
                            temperature=0.0, request_id="cap")
    res = cont.generate([req])[0]
    assert res.finish_reason == "length"
    assert 1 <= len(res.tokens) <= 5
    m = cont.get_metrics()
    assert m["capacity_finishes"] == 0       # old path: 1 (revive+retire)
    assert m["kv"]["pages_used"] == 0


def test_metrics_shape():
    cont = ContinuousEngine(SPEC, config=_cfg(), seed=0)
    m = cont.get_metrics()
    for k in ("total_requests", "waiting", "live_slots", "kv",
              "prefill", "decode_chunk", "attn_impl"):
        assert k in m, k


def test_batched_admission_single_prefill_dispatch():
    """N simultaneous cache-miss admissions share ONE prefill program
    call (serial per-request admission pays the fixed dispatch cost N
    times — the dominant admission cost on remote devices)."""
    import numpy as np

    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )
    from distributed_inference_engine_tpu.engine.types import GenerationRequest
    from distributed_inference_engine_tpu.models.llama import llama_spec

    spec = llama_spec("llama-tiny", max_seq_len=64)
    eng = ContinuousEngine(spec, config=EngineConfig(
        max_slots=4, max_seq_len=64, page_size=16, num_pages=64,
        decode_steps_per_call=4, attention_impl="xla"))
    rs = np.random.RandomState(3)
    reqs = [GenerationRequest(
        prompt=rs.randint(1, spec.vocab_size, size=5 + i).tolist(),
        max_new_tokens=4, temperature=0.0, request_id=f"b{i}")
        for i in range(4)]
    out = eng.generate(reqs)
    assert all(len(r.tokens) == 4 for r in out)
    assert eng.get_metrics()["prefill_calls"] == 1


def test_serving_metrics_ttft_and_occupancy():
    """SURVEY §5 serving metrics: per-request TTFT (measured from submit,
    so queue wait counts) and mean decode batch occupancy."""
    from distributed_inference_engine_tpu.config import EngineConfig
    from distributed_inference_engine_tpu.engine.continuous import (
        ContinuousEngine,
    )
    from distributed_inference_engine_tpu.engine.types import GenerationRequest
    from distributed_inference_engine_tpu.models.llama import llama_spec

    spec = llama_spec("llama-tiny", max_seq_len=64)
    eng = ContinuousEngine(spec, config=EngineConfig(
        max_slots=2, max_seq_len=64, page_size=16, num_pages=32,
        decode_steps_per_call=4, attention_impl="xla"))
    # 4 requests on 2 slots: the second wave queues behind the first
    out = eng.generate([GenerationRequest(
        prompt=[1 + i, 2, 3], max_new_tokens=8, temperature=0.0,
        request_id=f"q{i}") for i in range(4)])
    m = eng.get_metrics()
    assert m["ttft"]["count"] == 4
    assert 0.0 < m["batch_occupancy"] <= 1.0
    # queued requests' ttft includes their wait: their result ttft must be
    # at least the first wave's decode time (strictly > admission-only)
    ttfts = sorted(r.ttft_s for r in out)
    assert ttfts[-1] > ttfts[0]


def test_defer_sync_matches_synchronous_output():
    """defer_sync overlaps the packed readback with the next chunk's
    execution; outputs must be token-for-token the synchronous engine's,
    including mid-flight admissions and host-side stop sequences (which
    defer detects one chunk late but trims identically)."""
    rs = np.random.RandomState(7)
    # fully backed pool (defer requirement): 4 slots x 8 pages
    cfg = lambda **kw: _cfg(num_pages=32, **kw)
    sync = ContinuousEngine(SPEC, config=cfg(), seed=0)
    defer = ContinuousEngine(SPEC, params=sync.params,
                             config=cfg(defer_sync=True), seed=0)
    reqs = _reqs(rs, 3, max_new=14)
    reqs[1].stop_sequences = [[int(x)] for x in
                              sync.generate([_reqs(rs, 1)[0]])[0].tokens[:1]]
    sync2 = ContinuousEngine(SPEC, params=sync.params, config=cfg(), seed=0)

    def run(eng):
        ids = [eng.submit(r) for r in
               [GenerationRequest(prompt=r.prompt,
                                  max_new_tokens=r.max_new_tokens,
                                  stop_sequences=r.stop_sequences,
                                  request_id=r.request_id) for r in reqs[:2]]]
        eng.step()                              # mid-flight admission below
        ids.append(eng.submit(GenerationRequest(
            prompt=reqs[2].prompt, max_new_tokens=10, request_id="late")))
        out = {r.request_id: (r.tokens, r.finish_reason)
               for r in eng.run_until_idle()}
        return {i: out[i] for i in ids}

    assert run(sync2) == run(defer)


def test_streamed_run_matches_unstreamed_output():
    """Streaming decides nothing: with every request streamed (a chunk's
    tokens go out under the NEXT dispatch) the outputs are token-for-token
    the unstreamed engine's, including a mid-flight admission and a
    host-side stop sequence, and each stream splices to its result."""
    rs = np.random.RandomState(7)
    cfg = lambda **kw: _cfg(num_pages=32, **kw)
    plain = ContinuousEngine(SPEC, config=cfg(), seed=0)
    streamed = ContinuousEngine(SPEC, params=plain.params, config=cfg(),
                                seed=0)
    reqs = _reqs(rs, 3, max_new=14)
    reqs[1].stop_sequences = [[int(x)] for x in
                              plain.generate([_reqs(rs, 1)[0]])[0].tokens[:1]]
    plain2 = ContinuousEngine(SPEC, params=plain.params, config=cfg(), seed=0)

    def run(eng, stream):
        frames = {}

        def submit(r):
            frames[r.request_id] = []
            return eng.submit(r, on_tokens=(
                frames[r.request_id].append if stream else None))

        ids = [submit(GenerationRequest(
            prompt=r.prompt, max_new_tokens=r.max_new_tokens,
            stop_sequences=r.stop_sequences, request_id=r.request_id))
            for r in reqs[:2]]
        eng.step()                              # mid-flight admission below
        ids.append(submit(GenerationRequest(
            prompt=reqs[2].prompt, max_new_tokens=10, request_id="late")))
        out = {r.request_id: (r.tokens, r.finish_reason)
               for r in eng.run_until_idle()}
        if stream:
            for i in ids:
                assert [t for f in frames[i] for t in f] == out[i][0]
        return {i: out[i] for i in ids}

    assert run(plain2, False) == run(streamed, True)
    m = streamed.get_metrics()
    assert m["emit_carried_chunks"] >= 1
    assert (m["emit_carried_chunks"] + m["emit_flushed_chunks"]
            == m["decode_chunks"])


def test_defer_sync_requires_fully_backed_pool():
    import pytest

    with pytest.raises(ValueError, match="fully backed"):
        ContinuousEngine(SPEC, config=_cfg(defer_sync=True, num_pages=8))


def test_deferred_admission_parity_and_ttft():
    """Under decode pressure the deferred-admission path (first token
    installed device-side, harvested from the next chunk's packed read)
    must produce exactly the tokens of the sync path, with TTFT stamped
    and >=1 token per result."""
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    params = init_params(SPEC, jax.random.key(3))
    rs = np.random.RandomState(5)
    reqs = _reqs(rs, 4, max_new=10)

    def run(defer: bool):
        eng = ContinuousEngine(SPEC, params=params,
                               config=_cfg(defer_admission=defer))
        eng.submit(reqs[0])
        while not eng._slots:                  # r0 live -> pressure >= 1/4
            eng.step()
        for r in reqs[1:]:
            eng.submit(r)
        eng.step()                             # admission round for r1..r3
        if defer:
            assert eng.get_metrics()["deferred_admissions"] >= 3, \
                "deferred path did not engage"
        out = {r.request_id: r for r in eng.run_until_idle()}
        assert not any(getattr(s, "first_pending", False)
                       for s in eng._slots.values())
        return out

    got = run(True)
    ref = run(False)
    assert set(got) == set(ref)
    for rid in ref:
        assert got[rid].tokens == ref[rid].tokens, rid
        assert len(got[rid].tokens) >= 1
        assert got[rid].ttft_s > 0


def test_deferred_admission_single_token_request_falls_back():
    """max_new_tokens=1 must resolve with exactly one token even when the
    engine is busy (the deferred path cannot stop before decoding, so the
    admission round takes the sync path)."""
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    params = init_params(SPEC, jax.random.key(3))
    rs = np.random.RandomState(6)
    eng = ContinuousEngine(SPEC, params=params, config=_cfg())
    eng.submit(_reqs(rs, 1, max_new=12)[0])
    while not eng._slots:
        eng.step()
    one = GenerationRequest(prompt=[5, 6, 7], max_new_tokens=1,
                            temperature=0.0, request_id="one")
    eng.submit(one)
    out = {r.request_id: r for r in eng.run_until_idle()}
    assert len(out["one"].tokens) == 1


def test_deferred_admission_eos_first_token_stops_clean():
    """A deferred admission whose prefill-sampled first token IS eos must
    resolve as a stop with just that token — installed inactive on device
    (no dead decode steps) and retired at the next packed read."""
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    params = init_params(SPEC, jax.random.key(3))
    rs = np.random.RandomState(7)
    busy = _reqs(rs, 1, max_new=12)[0]
    probe = GenerationRequest(prompt=[9, 8, 7], max_new_tokens=6,
                              temperature=0.0, request_id="p")

    # discover the greedy first token for this prompt
    eng0 = ContinuousEngine(SPEC, params=params, config=_cfg())
    first = eng0.generate([probe])[0].tokens[0]

    def run(defer: bool):
        eng = ContinuousEngine(SPEC, params=params,
                               config=_cfg(defer_admission=defer))
        eng.submit(GenerationRequest(prompt=busy.prompt, max_new_tokens=12,
                                     temperature=0.0, request_id="busy"))
        while not eng._slots:
            eng.step()
        eng.submit(GenerationRequest(prompt=[9, 8, 7], max_new_tokens=6,
                                     temperature=0.0, eos_id=first,
                                     request_id="p"))
        out = {r.request_id: r for r in eng.run_until_idle()}
        if defer:
            assert eng.get_metrics()["deferred_admissions"] >= 1
        return out["p"]

    got, ref = run(True), run(False)
    assert got.finish_reason == ref.finish_reason == "stop"
    assert got.tokens == ref.tokens


def test_page_boundary_pause_revives_not_finishes():
    """A slot whose prompt + first chunk lands EXACTLY on a page boundary
    must pause and continue, not finish early (r5 verify catch): with
    page_size=16, chunk=4, a 12-token prompt had ensure_capacity grant
    exactly one page (12+4=16), the device stopped at the cap, and the
    harvest misread the pause as finish_reason="length" at 5/8 tokens."""
    rs = np.random.RandomState(3)
    # prompt 12 + chunk 4 == page_size 16: the historical failure shape
    req = [GenerationRequest(
        prompt=rs.randint(1, SPEC.vocab_size, size=12).tolist(),
        max_new_tokens=8, temperature=0.0, request_id="edge")]
    static = Engine(SPEC, config=_cfg(), seed=0)
    out_s = static.generate([GenerationRequest(
        prompt=list(req[0].prompt), max_new_tokens=8, temperature=0.0,
        request_id="edge")])
    cont = ContinuousEngine(SPEC, params=static.params, config=_cfg(),
                            seed=0)
    out_c = cont.generate(req)
    assert len(out_c[0].tokens) == 8, out_c[0].tokens
    assert out_c[0].tokens == out_s[0].tokens
    assert cont.get_metrics()["capacity_finishes"] == 0


def test_page_boundary_pause_revives_under_defer_sync():
    """Pause + revive through the deferred-readback path. Shape chosen so
    ensure_capacity's grant lands EXACTLY on a page boundary mid-flight
    (prompt 8, chunk 4, ahead 2x4: 8+8=16=page): the device pauses at
    the cap while the NEXT chunk is already dispatched with the slot
    inactive — that chunk's harvest sees a grown caps row and must not
    re-judge the paused slot as finished (the no-progress skip)."""
    rs = np.random.RandomState(3)
    req = [GenerationRequest(
        prompt=rs.randint(1, SPEC.vocab_size, size=8).tolist(),
        max_new_tokens=16, temperature=0.0, request_id="edge")]
    # defer_sync needs a fully backed pool: 4 slots * 8 pages
    cfg = _cfg(defer_sync=True, num_pages=32, max_seq_len=128)
    cont = ContinuousEngine(SPEC, config=cfg, seed=0)
    out = cont.generate(req)
    assert len(out[0].tokens) == 16, out[0].tokens


# ---------------------------------- the firsts host cache, device stop ids


def _plain_engine():
    spec = ModelSpec(
        vocab_size=256, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq_len=128, dtype="float32",
    )
    return ContinuousEngine(spec, config=EngineConfig(
        max_slots=2, max_seq_len=64, prefill_buckets=[16], page_size=16,
        num_pages=16, decode_steps_per_call=4), seed=0)


def _short_reqs(n=2, new=8):
    return [GenerationRequest(
        prompt=[(5 * i + j) % 250 + 1 for j in range(4 + 3 * i)],
        max_new_tokens=new, temperature=0.0, request_id=f"r{i}")
        for i in range(n)]


def test_firsts_snapshot_cache():
    """The packed chunk output carries the whole firsts buffer, so sync
    processing caches it host-side for free; rescue reads go through
    _firsts_snapshot() — one whole-buffer transfer at most, and the cache
    invalidates when an admission rewrites the device columns."""
    eng = _plain_engine()
    assert eng._firsts_host is None
    res = eng.generate(_short_reqs())
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
    eng.generate(_short_reqs())
    np.testing.assert_array_equal(eng._firsts_snapshot(),
                                  np.asarray(eng._firsts_dev))


def test_device_stop_ids():
    """stop_ids ride to the device as a [slots, K] matrix: the slot's row
    holds the ids (-1 padded), the decode loop exits at a hit, and the
    host trimmer keeps the matched stop (same contract as eos)."""
    eng = _plain_engine()
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
