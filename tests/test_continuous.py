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


def _copy(r, **over):
    kw = dict(prompt=list(r.prompt), max_new_tokens=r.max_new_tokens,
              temperature=0.0, eos_id=r.eos_id, stop_ids=r.stop_ids,
              stop_sequences=r.stop_sequences, request_id=r.request_id)
    kw.update(over)
    return GenerationRequest(**kw)


def _static(params, reqs, **cfg):
    """(tokens, reason) a request from the static engine: the reference
    the one sequence is held to."""
    static = Engine(SPEC, params=params, config=_cfg(**cfg), seed=0)
    return {r.request_id: (r.tokens, r.finish_reason)
            for i in range(0, len(reqs), 4)
            for r in static.generate([_copy(r) for r in reqs[i: i + 4]])}


def test_one_sequence_matches_the_static_engine():
    """Chunk k is read after chunk k+1 is dispatched; outputs must be
    token-for-token the static engine's, including a mid-flight admission
    and a host-side stop sequence (found one chunk late, trimmed
    identically)."""
    rs = np.random.RandomState(7)
    eng = ContinuousEngine(SPEC, config=_cfg(), seed=0)
    reqs = _reqs(rs, 3, max_new=14)
    reqs[1].stop_sequences = [[int(x)] for x in
                              eng.generate([_reqs(rs, 1)[0]])[0].tokens[:1]]
    reqs[2] = _copy(reqs[2], max_new_tokens=10, request_id="late")
    want = _static(eng.params, reqs)

    ids = [eng.submit(_copy(r)) for r in reqs[:2]]
    eng.step()                              # mid-flight admission below
    ids.append(eng.submit(_copy(reqs[2])))
    out = {r.request_id: (r.tokens, r.finish_reason)
           for r in eng.run_until_idle()}
    assert {i: out[i] for i in ids} == want
    assert eng._pending is None and not eng._first_reads


def test_streamed_run_matches_unstreamed_output():
    """Streaming decides nothing: with every request streamed (a chunk's
    tokens go out under the NEXT chunk) the outputs are token-for-token
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


def _pages_written(eng):
    """Physical pages of the K pool any row of which is not zero."""
    k = np.asarray(eng.kv.k_pages)              # [L, pages, page_size, fused]
    return set(np.nonzero(np.abs(k).sum(axis=(0, 2, 3)))[0].tolist())


def test_a_part_backed_pool_is_served_and_never_writes_past_its_pages():
    """8 pages for 4 slots of up to 8 pages each: nothing is refused at
    load. Where the pool cannot back a chunk ahead of the one in flight
    the iteration reads that chunk first (``sync_fallback_iterations``),
    outputs are the static engine's, and no page is written that the
    allocator did not hand to a live slot at that dispatch."""
    rs = np.random.RandomState(11)
    eng = ContinuousEngine(SPEC, config=_cfg(num_pages=8), seed=0)
    reqs = _reqs(rs, 4, prompt_len=12, max_new=18)
    want = _static(eng.params, reqs)
    for r in reqs:
        eng.submit(_copy(r))
    out, held = {}, set()
    while eng.n_live or eng.n_waiting:
        eng.step()
        held |= {p for pages in eng.kv._slot_pages.values() for p in pages}
        assert _pages_written(eng) <= held
        out.update({r.request_id: (r.tokens, r.finish_reason)
                    for r in eng.drain_finished()})
    assert out == want
    m = eng.get_metrics()
    assert m["sync_fallback_iterations"] > 0
    assert m["capacity_finishes"] == 0 and m["kv"]["pages_used"] == 0


def test_eight_requests_over_four_slots_hand_their_slots_on():
    """Every request ends by ``max_new_tokens``, which the host foresees:
    the four successors are prefilled behind the chunk their predecessors
    end in, no decode chunk is sent with an empty slot, and the tokens are
    the static engine's."""
    rs = np.random.RandomState(12)
    eng = ContinuousEngine(SPEC, config=_cfg(), seed=0)
    reqs = _reqs(rs, 8, max_new=11)
    want = _static(eng.params, reqs)
    out = {r.request_id: (r.tokens, r.finish_reason)
           for r in eng.generate([_copy(r) for r in reqs])}
    assert out == want
    m = eng.get_metrics()
    assert m["admissions"] == 8 and m["admissions_ahead"] == 4
    assert m["empty_slot_dispatches"] == 0
    assert m["finishes_learned_late"] == 0
    assert m["sync_fallback_iterations"] == 0
    # 1 first + 10 decoded over chunks of 4: three chunks a wave, none
    # of them empty for a slot
    assert m["decode_chunks"] == 6


def test_an_eos_finish_is_learned_late_and_leaks_nothing():
    """An EOS is learned at the read: it costs its slot one chunk
    (``finishes_learned_late``), nothing past it is emitted or streamed,
    and the slot's next tenant gets none of the old request's column."""
    rs = np.random.RandomState(2)
    eng = ContinuousEngine(SPEC, config=_cfg(max_slots=1), seed=0)
    a, b = _reqs(rs, 2, max_new=12)
    probe = _static(eng.params, [a, b])
    eos = probe["r0"][0][5]
    cut = probe["r0"][0].index(eos) + 1
    frames = []
    eng.submit(_copy(a, eos_id=eos), on_tokens=frames.append)
    eng.submit(_copy(b))
    out = {r.request_id: r for r in eng.run_until_idle()}
    assert out["r0"].tokens == probe["r0"][0][:cut]
    assert out["r0"].finish_reason == "stop"
    assert [t for f in frames for t in f] == out["r0"].tokens
    assert (out["r1"].tokens, out["r1"].finish_reason) == probe["r1"]
    m = eng.get_metrics()
    assert m["finishes_learned_late"] == 1 and m["admissions_ahead"] == 0


def test_first_frame_leaves_before_the_read_of_the_chunk_that_follows():
    """An admission's first token is read from its prefill's own output
    right after the next decode dispatch: its first frame is delivered
    before that chunk's packed output is read, with the engine busy or
    idle, and TTFT is stamped then."""
    rs = np.random.RandomState(5)
    eng = ContinuousEngine(SPEC, config=_cfg(), seed=0)
    log = []
    harvest = eng._harvest_chunk

    def spy(entry):
        log.append(("read", id(entry)))
        return harvest(entry)

    eng._harvest_chunk = spy
    busy, new = _reqs(rs, 2, max_new=16)
    want = _static(eng.params, [busy, new])
    for req in (busy, new):
        eng.submit(_copy(req), on_tokens=lambda toks, rid=req.request_id:
                   log.append(("frame", rid, list(toks))))
        eng.step()                      # admits it, dispatches, reads
        follows = id(eng._pending)      # the chunk sent behind its prefill
        first = log.index(("frame", req.request_id,
                           want[req.request_id][0][:1]))
        assert ("read", follows) not in log[:first]
        state = next(s for s in eng._slots.values()
                     if s.request.request_id == req.request_id)
        assert not state.first_pending and state.first_token_at > 0
        eng.step()
        assert ("read", follows) in log
    out = {r.request_id: (r.tokens, r.finish_reason)
           for r in eng.run_until_idle()}
    assert out == want
    assert eng.get_metrics()["ttft"]["count"] == 2


def test_admissions_under_decode_pressure_match_the_static_engine():
    """Admissions into a busy engine (first tokens installed device-side,
    read after the next dispatch) produce exactly the static engine's
    tokens, with TTFT stamped and >=1 token per result."""
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    params = init_params(SPEC, jax.random.key(3))
    rs = np.random.RandomState(5)
    reqs = _reqs(rs, 4, max_new=10)
    eng = ContinuousEngine(SPEC, params=params, config=_cfg())
    want = _static(eng.params, reqs)
    eng.submit(_copy(reqs[0]))
    while not eng._slots:
        eng.step()
    for r in reqs[1:]:
        eng.submit(_copy(r))
    eng.step()                             # admission round for r1..r3
    assert not any(s.first_pending for s in eng._slots.values())
    got = {r.request_id: r for r in eng.run_until_idle()}
    assert set(got) == set(want)
    for rid in want:
        assert (got[rid].tokens, got[rid].finish_reason) == want[rid], rid
        assert len(got[rid].tokens) >= 1
        assert got[rid].ttft_s > 0


def test_single_token_request_never_decodes_and_holds_no_slot():
    """max_new_tokens=1 resolves with exactly one token with the engine
    busy: its row is never installed, its slot goes back at once, and the
    admission does not wait for the token."""
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    params = init_params(SPEC, jax.random.key(3))
    rs = np.random.RandomState(6)
    eng = ContinuousEngine(SPEC, params=params, config=_cfg(max_slots=2))
    busy = _reqs(rs, 1, max_new=12)[0]
    one = GenerationRequest(prompt=[5, 6, 7], max_new_tokens=1,
                            temperature=0.0, request_id="one")
    want = _static(eng.params, [busy, one])
    eng.submit(_copy(busy))
    while not eng._slots:
        eng.step()
    eng.submit(_copy(one))
    eng.step()
    assert len(eng._slots) == 1 and eng.kv.n_free_slots == 1
    out = {r.request_id: (r.tokens, r.finish_reason)
           for r in eng.drain_finished() + eng.run_until_idle()}
    assert out == want and len(out["one"][0]) == 1


def test_eos_first_token_stops_clean():
    """An admission whose prefill-sampled first token IS eos resolves as a
    stop with just that token — installed inactive on device (no dead
    decode steps) and retired when the host reads the token."""
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    params = init_params(SPEC, jax.random.key(3))
    rs = np.random.RandomState(7)
    busy = _reqs(rs, 1, max_new=12)[0]
    eng = ContinuousEngine(SPEC, params=params, config=_cfg())
    probe = GenerationRequest(prompt=[9, 8, 7], max_new_tokens=6,
                              temperature=0.0, request_id="p")
    first = _static(eng.params, [probe])["p"][0][0]
    eng.submit(_copy(busy))
    while not eng._slots:
        eng.step()
    eng.submit(_copy(probe, eos_id=first))
    eng.step()
    got = {r.request_id: r for r in eng.drain_finished()}["p"]
    assert (got.tokens, got.finish_reason) == ([first], "stop")
    assert eng.get_metrics()["finishes_learned_late"] == 1
    rest = eng.run_until_idle()
    assert [(r.tokens, r.finish_reason) for r in rest] == [
        _static(eng.params, [busy])[busy.request_id]]


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


def test_a_grant_never_ends_where_a_chunk_does():
    """Under a chunk in flight a pause at the grant costs the row the next
    chunk, so the capacity loop asks for one row more than the chunks can
    write: a prompt of 8 with chunks of 4 (8+4+4 = one page of 16) runs
    through the page boundary without a pause, and equals the static
    engine."""
    rs = np.random.RandomState(3)
    req = GenerationRequest(
        prompt=rs.randint(1, SPEC.vocab_size, size=8).tolist(),
        max_new_tokens=16, temperature=0.0, request_id="edge")
    cont = ContinuousEngine(SPEC, config=_cfg(), seed=0)
    want = _static(cont.params, [req])
    revived = []
    set_active = cont._set_active
    cont._set_active = lambda slots, value: (
        revived.extend(slots if value else []), set_active(slots, value))
    out = cont.generate([_copy(req)])
    assert (out[0].tokens, out[0].finish_reason) == want["edge"]
    assert not revived and cont.get_metrics()["decode_chunks"] == 4


def test_a_row_paused_at_its_grant_is_revived_a_chunk_later():
    """Pool pressure can still end a grant inside a chunk: the device
    pauses the row, the read (one chunk behind) revives it, the chunk
    already in flight carries it idle and must not re-judge it as a
    finished "length" (the no-progress skip)."""
    rs = np.random.RandomState(3)
    req = GenerationRequest(
        prompt=rs.randint(1, SPEC.vocab_size, size=8).tolist(),
        max_new_tokens=16, temperature=0.0, request_id="edge")
    cont = ContinuousEngine(SPEC, config=_cfg(), seed=0)
    want = _static(cont.params, [req])
    # the allocator grants what is asked and no row more, as a pool with
    # one page to spare would
    ensure = cont.kv.ensure_capacity
    cont.kv.ensure_capacity = lambda slot, total: ensure(slot, total - 1)
    idle = []
    judge = cont._judge_packed

    def spy(entry, packed, progressed):
        idle.extend(s for s, p in progressed.items() if not p)
        return judge(entry, packed, progressed)

    cont._judge_packed = spy
    out = cont.generate([_copy(req)])
    assert (out[0].tokens, out[0].finish_reason) == want["edge"]
    assert idle and cont.get_metrics()["capacity_finishes"] == 0


# ----------------------------------------------------------- device stop ids


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
