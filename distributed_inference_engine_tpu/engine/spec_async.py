"""Bubble-scheduled asynchronous speculation (ISSUE 15 / ROADMAP 5).

Round 5's *synchronous* speculative engine measured 0.80x against the
int4 flagship: every draft+verify round sits ON the critical path, so
the draft's latency is paid even when acceptance is high. PipeInfer
(PAPERS.md) inverts the schedule — draft in the HOST GAPS between the
serving engine's device dispatches, verify by piggybacking the drafted
tokens onto the next megastep as extra query columns — so the draft
model's compute hides in time the device was idle anyway and the only
on-path cost is the (wider, still one-dispatch) verify step.

``AsyncSpeculator`` layers that schedule over ``ContinuousEngine``:

- **Drafting** runs a small draft model (a truncated self-draft by
  default — ``engine.speculative.truncated_draft`` — or an r13 serving
  artifact via ``spec_draft_model="artifact:<path>"``) over dense
  per-slot caches, for STREAMING-flagged slots only: batch-throughput
  traffic gains nothing from speculation (the batch already fills the
  device) while latency-priced streams are exactly where accepted
  drafts compress inter-token latency.
- **Scheduling** is bubble-budgeted: ``schedule()`` is called from the
  serving pump's overlap hook (right after ``poll_stream()``, while a
  chunk is in flight) and from the engine's step top (the gap between
  dispatch brackets). Each call first estimates the live per-step host
  bubble from ``obs.timeline.busy_gap_split`` (falling back to the
  engine's dispatch/gap accumulators when the timeline ring is off) and
  SKIPS the round when the estimate is below
  ``EngineConfig.spec_bubble_floor_s`` — at saturation the gap
  collapses, the estimate falls under the floor, and speculation
  auto-idles to zero overhead (the ``auto_idles`` counter is the
  regression guard).
- **Verification is asynchronous**: proposals never block. They are
  parked on device (``_drafts``/``_qprobs``) and ride the NEXT decode
  step as extra verify columns through the ragged mixed-step path
  (``ContinuousEngine._verify_chunk``); acceptance is the shared
  rejection-sampling rule in ``engine.spec_accept``, so greedy output
  is token-for-token the non-speculative engine's.

Correctness never depends on the draft. The verify step recomputes the
target distribution at every position, so a stale basis, a clamped
draft cache, or plain garbage proposals can only lower the ACCEPTANCE
rate — the emitted tokens are always target-model tokens. That one
property keeps every edge case here (slot reuse, mid-flight
invalidation, capacity-clipped windows) a performance concern, not a
correctness one; the engine drops invalidated proposals and counts
them in ``wasted_tokens``.

Draft-cache bookkeeping (the catch-up/propose split):

- ``_dlen[slot]`` is the draft KV's valid prefix: positions
  ``[0, _dlen)`` hold KV for the COMMITTED sequence (admitted prompt +
  harvested tokens). The host always knows that sequence, so catch-up
  needs no device reads: it forwards the missing window
  ``seq[_dlen : total]`` through ``models.base.forward_window`` (ragged
  ``n_valid``, out-of-range scatters dropped).
- Catch-up is always safe — committed tokens never change — so it runs
  even while a chunk is in flight (the overlap-hook call). PROPOSING
  needs a frontier basis: it runs only when no chunk is in flight
  (``engine._inflight_chunks == 0``, i.e. the step-top call) and no
  proposal is already pending, drafts ``spec_max_draft`` tokens in one
  scan, and records the basis ``(L, last_token)`` per slot. The verify
  step re-checks that basis against the live host state; any mismatch
  (a mixed step advanced the slot, a swap, slot reuse) wastes the
  proposal, nothing more.
- Steady state is one catch-up token per accepted run: the verify
  step's bonus/rejection token is sampled from the TARGET distribution,
  so the draft has never seen it — the next round's deficit is 1.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import ModelSpec, Params, forward_window
from ..obs.timeline import busy_gap_split
from ..ops.sampling import SamplingParams
from ..utils.hotpath import hot_path
from .spec_accept import draft_sample

__all__ = ["AsyncSpeculator", "resolve_draft"]


def resolve_draft(spec: ModelSpec, params: Params, name: str,
                  ) -> Tuple[ModelSpec, Params]:
    """Build (draft_spec, draft_params) from ``EngineConfig
    .spec_draft_model``:

    - ``"layers:N"`` (and ``""`` → ``layers:2``): truncated self-draft —
      the target's own first N blocks with shared embeddings/head
      (``engine.speculative.truncated_draft``; works on the engine's
      already-prepared tree, QuantizedTensor leaves slice payload and
      scales together).
    - ``"artifact:<path>"``: an r13 serving artifact
      (``engine/artifact.py``) — the cold-start path for a real trained
      drafter; the sidecar tree is already post-``prepare_params``.

    The draft must share the target's vocabulary: acceptance compares
    per-token probabilities index-by-index.
    """
    from .speculative import truncated_draft

    name = name or "layers:2"
    if name.startswith("artifact:"):
        from .artifact import load_artifact

        d_spec, d_params, _ = load_artifact(name.split(":", 1)[1])
        if d_spec.vocab_size != spec.vocab_size:
            raise ValueError(
                f"draft vocab {d_spec.vocab_size} != target vocab "
                f"{spec.vocab_size}: rejection sampling compares "
                "distributions index-by-index")
        return d_spec, d_params
    if name.startswith("layers:"):
        n = int(name.split(":", 1)[1])
        if spec.n_layers < 2:
            raise ValueError(
                "spec_async truncated self-draft needs n_layers >= 2 "
                "(pass spec_draft_model='artifact:...' for a 1-layer "
                "target)")
        n = max(1, min(n, spec.n_layers - 1))
        return truncated_draft(spec, params, n)
    raise ValueError(
        f"spec_draft_model {name!r} is not 'layers:N'|'artifact:<path>'")


class AsyncSpeculator:
    """Drafter subsystem over one ``ContinuousEngine`` (module doc)."""

    # catch-up window pow2 buckets: the whole run compiles at most
    # len(buckets) x {catch-up, propose} draft programs. Steady state
    # lives in the smallest bucket (deficit 1 = the bonus token); the
    # large bucket drains fresh prompts a window at a time.
    _W_BUCKETS = (8, 64)

    def __init__(self, engine: Any, draft_spec: ModelSpec,
                 draft_params: Params, *, k: int,
                 bubble_floor_s: float, seed: int = 0) -> None:
        if k < 1:
            raise ValueError(f"spec_max_draft {k} < 1")
        self.engine = engine
        self.draft_spec = draft_spec
        self.draft_params = draft_params
        self.k = int(k)
        self.bubble_floor_s = float(bubble_floor_s)
        self._rng = jax.random.key(seed ^ 0x5bec)

        b = engine.max_slots
        cfg = engine.config
        # dense per-slot draft caches ([L, max_slots, S, Hkv, Dh] — the
        # sync engine's layout, reused so forward_window serves both).
        # +k+1 headroom: proposal KV lands past the committed frontier;
        # forward_window's mode="drop" scatter bounds everything else.
        s_d = min(cfg.max_seq_len, engine.spec.max_seq_len) + self.k + 1
        dt = jnp.dtype(cfg.kv_dtype)
        shape = (draft_spec.n_layers, b, s_d, draft_spec.n_kv_heads,
                 draft_spec.head_dim)
        self._S = s_d
        self._dck = jnp.zeros(shape, dt)
        self._dcv = jnp.zeros(shape, dt)

        # host mirrors: valid draft-KV prefix per slot row, the _Slot
        # identity the row belongs to (slot ids are reused), and the
        # ADMITTED prompt (overlong prompts keep their tail — the
        # engine's clamp, re-derived from prompt_len)
        self._dlen = np.zeros((b,), np.int64)
        self._ident: Dict[int, Any] = {}
        self._prompt: Dict[int, List[int]] = {}
        # pending proposals: slot -> (basis L, basis last token). The
        # proposal tensors stay ON DEVICE until the verify step consumes
        # them — drafting costs zero host syncs.
        self._pending: Dict[int, Tuple[int, int]] = {}
        self._drafts: Optional[jnp.ndarray] = None    # [B, k] int32
        self._qprobs: Optional[jnp.ndarray] = None    # [B, k, V] f32

        # metrics (engine.get_metrics exports these as spec_async_*)
        self._drafted_tokens = 0
        self._accepted_tokens = 0
        self._wasted_tokens = 0
        self._catchup_tokens = 0
        self._draft_rounds = 0
        self._propose_rounds = 0
        self._auto_idles = 0
        self._bubble_consumed_s = 0.0
        self._cost_ema: Optional[float] = None
        # accumulator-fallback bubble estimate state
        self._gap_mark = (0.0, 0)
        self._last_est = 0.0

        d_spec = draft_spec
        kk = self.k

        @partial(jax.jit, static_argnames=("w", "propose"),
                 donate_argnums=(1, 2))
        def _round(params, dck, dcv, tokens, n_valid, start, sampling,
                   key, w: int, propose: bool):
            """One draft round: catch the per-slot caches up over a
            ragged token window, then (propose=True) autoregress ``k``
            proposals. Rows not participating pass ``start = S`` — every
            scatter lands out of range and drops; their outputs are
            garbage the host never reads. ``w`` is the pow2 window
            bucket (static → one program per (bucket, propose))."""
            del w
            logits, dck, dcv = forward_window(
                d_spec, params, tokens, n_valid, start, dck, dcv)
            if not propose:
                return dck, dcv
            b_ = tokens.shape[0]
            # distribution AFTER the last caught-up token (= after the
            # committed frontier token for propose rows)
            q_logits = logits[jnp.arange(b_),
                              jnp.maximum(n_valid - 1, 0)]
            greedy = sampling.temperature <= 0.0
            pos0 = (start + n_valid).astype(jnp.int32)
            one = jnp.ones((b_,), jnp.int32)

            def prop(carry, step_key):
                dck, dcv, q_logits, pos = carry
                d_tok, q_probs = draft_sample(
                    q_logits, sampling, greedy, step_key)
                nxt, dck, dcv = forward_window(
                    d_spec, params, d_tok[:, None], one, pos, dck, dcv)
                return (dck, dcv, nxt[:, 0], pos + 1), (d_tok, q_probs)

            keys = jax.random.split(key, kk)
            (dck, dcv, _, _), (dr, qp) = jax.lax.scan(
                prop, (dck, dcv, q_logits, pos0), keys)
            return dck, dcv, dr.T, jnp.swapaxes(qp, 0, 1)

        self._round = _round

    # ------------------------------------------------------------ budget

    def _bubble_estimate(self) -> float:
        """Live per-step host-bubble estimate, in seconds.

        Timeline ring on: ``busy_gap_split`` over the most recent
        records — gap seconds per inter-dispatch gap. Ring off: delta of
        the engine's always-on ``_host_gap_s`` accumulator over the
        steps since the last estimate. Cold start (nothing measured)
        reads 0.0, so a positive floor idles the drafter until real gap
        data exists — the conservative direction."""
        eng = self.engine
        tl = eng.timeline
        if tl is not None:
            ev = [e for e in tl.events() if e.get("dispatch")]
            if len(ev) < 2:
                return 0.0
            split = busy_gap_split(ev[-32:])
            return split["gap_s"] / max(1, split["n_events"] - 1)
        steps = (eng._steps + eng._mixed_steps
                 + getattr(eng, "_spec_verify_steps", 0))
        d_gap = eng._host_gap_s - self._gap_mark[0]
        d_n = steps - self._gap_mark[1]
        if d_n <= 0:
            return self._last_est
        self._gap_mark = (eng._host_gap_s, steps)
        self._last_est = d_gap / d_n
        return self._last_est

    # ------------------------------------------------------- host mirror

    def _sync_ident(self) -> None:
        """Reconcile slot rows with the engine's live ``_Slot`` objects:
        finished/reused slots reset their draft row (dlen=0) and waste
        any pending proposal; new slots cache their ADMITTED prompt."""
        eng = self.engine
        for slot in list(self._ident):
            st = eng._slots.get(slot)
            if st is None or st is not self._ident[slot]:
                del self._ident[slot]
                self._prompt.pop(slot, None)
                self._dlen[slot] = 0
                if self._pending.pop(slot, None) is not None:
                    self._wasted_tokens += self.k
        for slot, st in eng._slots.items():
            if slot not in self._ident:
                self._ident[slot] = st
                self._dlen[slot] = 0
                p = st.request.prompt
                self._prompt[slot] = (
                    list(p) if len(p) == st.prompt_len
                    else list(p[-st.prompt_len:]))

    def _seq_tok(self, slot: int, st: Any, i: int) -> int:
        p = self._prompt[slot]
        return p[i] if i < len(p) else int(st.tokens[i - len(p)])

    # --------------------------------------------------------- schedule

    @hot_path
    def schedule(self) -> int:
        """One bubble-budgeted draft round; returns rows worked.

        Called from the pump's overlap hook (after ``poll_stream()``;
        catch-up only — a chunk is in flight, so the frontier is about
        to move) and from the engine's step top (the inter-dispatch gap;
        the host state IS the frontier, so proposing is allowed). The
        round is one async device dispatch — no host syncs — so an
        overrun queues behind the next chunk instead of delaying its
        dispatch."""
        eng = self.engine
        if not eng._slots:
            return 0
        t_start = time.perf_counter()
        self._sync_ident()
        est = self._bubble_estimate()
        if est < self.bubble_floor_s:
            self._auto_idles += 1
            return 0
        can_propose = (eng._inflight_chunks == 0 and not self._pending)
        wmax = self._W_BUCKETS[-1]
        rows: List[Tuple[int, int, int]] = []     # (slot, start, cat)
        propose_rows: List[int] = []
        for slot, st in eng._slots.items():
            if st.on_tokens is None or st.first_pending:
                continue     # speculation serves streaming slots only
            total = st.prompt_len + len(st.tokens)      # = L + 1
            deficit = total - int(self._dlen[slot])
            if can_propose and deficit <= wmax:
                # deficit 0 (proposal was wasted without the slot
                # moving): re-forward the frontier token — idempotent KV
                # write, recovers the propose distribution
                start = total - 1 if deficit <= 0 else int(
                    self._dlen[slot])
                rows.append((slot, start, total - start))
                propose_rows.append(slot)
            elif deficit > 0:
                start = int(self._dlen[slot])
                rows.append((slot, start, min(deficit, wmax)))
        if not rows:
            return 0

        w = self._W_BUCKETS[0]
        need = max(c for _, _, c in rows)
        for b_ in self._W_BUCKETS:
            if b_ >= need:
                w = b_
                break
        b = eng.max_slots
        tok_m = np.zeros((b, w), np.int32)
        n_valid = np.zeros((b,), np.int32)
        start_v = np.full((b,), self._S, np.int32)   # sentinel: drop all
        for slot, start, cat in rows:
            st = eng._slots[slot]
            tok_m[slot, :cat] = [self._seq_tok(slot, st, i)
                                 for i in range(start, start + cat)]
            n_valid[slot] = cat
            start_v[slot] = start

        sampling = SamplingParams(eng._temps, eng._top_k, eng._top_p,
                                  eng._min_p)
        self._rng, kr = jax.random.split(self._rng)
        do_prop = bool(propose_rows)
        out = self._round(self.draft_params, self._dck, self._dcv,
                          jnp.asarray(tok_m), jnp.asarray(n_valid),
                          jnp.asarray(start_v), sampling, kr,
                          w=w, propose=do_prop)
        if do_prop:
            self._dck, self._dcv, self._drafts, self._qprobs = out
        else:
            self._dck, self._dcv = out

        for slot, start, cat in rows:
            self._dlen[slot] = start + cat
            self._catchup_tokens += cat
        for slot in propose_rows:
            st = eng._slots[slot]
            total = st.prompt_len + len(st.tokens)
            self._pending[slot] = (
                total - 1, self._seq_tok(slot, st, total - 1))
            self._drafted_tokens += self.k
        self._draft_rounds += 1
        self._propose_rounds += do_prop
        dt = time.perf_counter() - t_start
        self._bubble_consumed_s += dt
        self._cost_ema = (dt if self._cost_ema is None
                          else 0.8 * self._cost_ema + 0.2 * dt)
        return len(rows)

    # ----------------------------------------------------------- verify

    def take_verifiable(self):
        """Consume pending proposals for the next decode step. Returns
        ``(drafts_dev, qprobs_dev, n_drafts, verified)`` — ``n_drafts``
        is a per-slot column count (0 = plain decode row) and
        ``verified`` maps slot -> (basis L, columns granted) — or None
        when nothing survives the freshness + capacity checks.

        Freshness: the recorded basis must still be the slot's live
        frontier (same ``_Slot``, same committed length, same last
        token). Capacity: the verify window writes KV at
        ``[L, L + m + 1)``, so columns are clipped to the slot's page
        grant — writing through a stale page-table entry would corrupt
        OTHER slots, the one draft failure mode that is not
        performance-only. Every drop or clip lands in
        ``wasted_tokens``."""
        if not self._pending:
            return None
        eng = self.engine
        self._sync_ident()                 # drops dead/reused slots
        n_drafts = np.zeros((eng.max_slots,), np.int32)
        verified: Dict[int, Tuple[int, int]] = {}
        for slot, (basis_len, basis_last) in list(self._pending.items()):
            del self._pending[slot]
            st = eng._slots.get(slot)
            if st is None or self._ident.get(slot) is not st:
                self._wasted_tokens += self.k
                continue
            total = st.prompt_len + len(st.tokens)
            fresh = (total - 1 == basis_len
                     and self._seq_tok(slot, st, basis_len) == basis_last)
            cap_tok = min(eng.kv.slot_capacity(slot), eng.max_seq_len)
            m = max(0, min(self.k, cap_tok - basis_len - 1))
            if not fresh or m <= 0:
                self._wasted_tokens += self.k
                continue
            self._wasted_tokens += self.k - m
            n_drafts[slot] = m
            verified[slot] = (basis_len, m)
        if not verified:
            return None
        return self._drafts, self._qprobs, n_drafts, verified

    def note_verified(self, entry: Any, verified: Dict[int, Tuple[int,
                                                                  int]],
                      ) -> None:
        """Post-verify bookkeeping from the chunk's packed host read
        (``entry.host`` — zero extra device syncs): acceptance counters
        and the draft-KV validity extension. ``n_acc`` is clipped to
        tokens actually EMITTED (budget/cap/eos cuts discard accepted
        tokens; greedy re-derives them identically later, sampled rows
        re-sample — either way the draft KV past the committed frontier
        may no longer match, so only the emitted prefix extends
        ``_dlen``)."""
        n = entry.n_steps
        acc_row = entry.host[2 * n + 4]
        toks = entry.host[:n]
        eng = self.engine
        for slot, (basis_len, m) in verified.items():
            n_acc = int(acc_row[slot])
            emitted = int((toks[:, slot] >= 0).sum())
            n_eff = max(0, min(n_acc, m, emitted))
            self._accepted_tokens += n_eff
            self._wasted_tokens += m - n_eff
            st = eng._slots.get(slot)
            if st is not None and self._ident.get(slot) is st:
                total = st.prompt_len + len(st.tokens)
                self._dlen[slot] = min(basis_len + 1 + n_eff, total)

    # ---------------------------------------------------------- metrics

    def get_metrics(self) -> Dict[str, Any]:
        drafted = self._drafted_tokens
        return {
            "drafted_tokens": drafted,
            "accepted_tokens": self._accepted_tokens,
            "wasted_tokens": self._wasted_tokens,
            "catchup_tokens": self._catchup_tokens,
            "accept_rate": (self._accepted_tokens / drafted
                            if drafted else 0.0),
            "draft_rounds": self._draft_rounds,
            "propose_rounds": self._propose_rounds,
            "auto_idles": self._auto_idles,
            "bubble_consumed_s": self._bubble_consumed_s,
            "draft_cost_ema_s": self._cost_ema or 0.0,
            "pending": len(self._pending),
        }
