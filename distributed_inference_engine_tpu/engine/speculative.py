"""Speculative decoding: a small draft model proposes, the target verifies.

No reference counterpart (the reference's "model" is an asyncio sleep,
SURVEY.md §2.2) — this is a pure serving-throughput technique for the real
engine: decode is HBM-bandwidth-bound, so scoring k draft tokens in ONE
target forward (``models.base.forward_window``) converts k serial
weight-streaming passes into one, at the cost of running a much smaller
draft model serially.

Algorithm (Leviathan et al. / Chen et al. rejection sampling):

1. **Draft catch-up + proposal.** The draft syncs its KV cache over the ≤2
   tokens it hasn't processed (one windowed forward), then proposes
   ``k`` tokens autoregressively, recording its distribution q_i for each.
2. **Target verify.** One windowed target forward over
   ``[last, d_0 … d_{k-1}]`` yields p_0 … p_k and writes the window's KV.
3. **Accept.** Greedy requests accept while ``argmax p_i == d_i`` — the
   output is TOKEN-FOR-TOKEN the target's own greedy chain. Sampled
   requests accept d_i with prob ``min(1, p_i[d_i]/q_i[d_i])`` and resample
   the first rejection from ``norm(max(p−q, 0))``. Both p and q are the
   KNOB-MODIFIED distributions (temperature, then top-k/top-p/min-p masks,
   renormalized — ``ops.sampling.masked_sampling_probs``): rejection
   sampling is exact for whatever target distribution the acceptance ratio
   uses, so masking p with the request's knobs makes the output
   distributionally identical to the static engines' sampler, and masking
   q the same way keeps the draft proposing inside the target's support
   (acceptance never degrades from the draft proposing masked-out tokens).
4. Rejected positions leave garbage KV past the accepted length in both
   caches; it is masked by the length bookkeeping and overwritten by the
   next round.

Everything is static-shape: one jitted round per (batch-bucket, cache
bucket), scanned on device; the host loop only checks "anyone still
active" per round (SURVEY.md §7 hard-part #1 discipline).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import EngineConfig
from ..models.base import (
    ModelSpec,
    Params,
    forward_prefill,
    forward_window,
    init_params,
    unembed,
)
from ..ops.sampling import (
    SamplingParams,
    masked_sampling_probs,
    sample_tokens_with_logprobs,
)
from ..obs.timeline import StepTimeline, host_span
from ..utils.hotpath import hot_path
from ..utils.tracing import LatencyStats
from .engine import _next_bucket, _pow2_buckets
from .spec_accept import draft_sample, rejection_accept
from .types import (
    GenerationRequest,
    GenerationResult,
    scan_host_stops,
    trim_at_stops,
)

logger = logging.getLogger(__name__)


def truncated_draft(spec: ModelSpec, params: Params,
                    n_layers: int) -> tuple:
    """Build a draft from the TARGET's own weights truncated to its first
    ``n_layers`` blocks (embeddings, final norm, and LM head shared).

    The standard random-init benchmarking problem: an independently
    initialized draft agrees with the target near-never, so acceptance —
    and therefore the whole speculative speedup — is unmeasurable. A
    truncated self-draft shares the target's early-layer computation by
    construction, giving deterministic, structurally meaningful agreement
    with zero extra training artifacts (VERDICT r2 item 4's prescription).
    With real checkpoints the same helper yields a "skip the top layers"
    draft — a known cheap-draft family (cf. self-speculative decoding).

    Works for quantized trees: ``QuantizedTensor`` leaves slice their int8
    payload and per-channel scales along the stacked layer axis together.
    """
    from ..ops.quant import QuantizedTensor

    L = spec.n_layers
    if not 1 <= n_layers < L:
        raise ValueError(f"draft layers {n_layers} not in [1, {L})")
    d_spec = spec.replace(n_layers=n_layers)

    def cut(x):
        if isinstance(x, QuantizedTensor):
            s = x.s[:n_layers] if x.s.shape and x.s.shape[0] == L else x.s
            # bits/pack_axis ride along (pack_axis is end-relative, so the
            # leading-layer slice leaves it valid)
            return dataclasses.replace(x, q=x.q[:n_layers], s=s)
        return x[:n_layers]

    d_params = dict(params)                 # non-block leaves shared
    d_params["blocks"] = {k: cut(v) for k, v in params["blocks"].items()}
    return d_spec, d_params


def scale_top_blocks(spec: ModelSpec, params: Params, n_shared: int,
                     eps: float) -> Params:
    """ε-noise target for acceptance sweeps: blocks ``>= n_shared`` get
    their residual-writing weights (``wo``, ``w_down``, and their biases)
    scaled by ``eps``, so each such block perturbs the residual stream by
    O(eps) instead of O(1).

    Paired with ``truncated_draft(spec, params, n_shared)`` this gives a
    CHEAP draft whose agreement with the target is a measurable function
    of eps: at eps=0 the top blocks are exact identities (zero residual
    contribution; embeddings/final norm/lm head shared), so target logits
    equal draft logits and greedy acceptance is exactly 1 — the
    machinery-ceiling point; eps→1 recovers the unrelated-top-layers
    regime where acceptance collapses. Sweeping eps traces tok/s vs
    acceptance on hardware (examples/spec_sweep.py) with no second param
    set: quantized trees scale only the per-channel scale arrays (the
    int8/int4 payload is shared).
    """
    from ..ops.quant import QuantizedTensor

    L = spec.n_layers
    if not 0 < n_shared < L:
        raise ValueError(f"n_shared {n_shared} not in (0, {L})")
    blocks = dict(params["blocks"])
    for name in ("wo", "w_down", "bo", "b_down"):
        w = blocks.get(name)
        if w is None:
            continue
        if isinstance(w, QuantizedTensor):
            blocks[name] = dataclasses.replace(
                w, s=w.s.at[n_shared:].multiply(eps))
        else:
            blocks[name] = w.at[n_shared:].multiply(eps)
    return {**params, "blocks": blocks}


class SpeculativeEngine:
    """Engine-interface implementation (same ``generate`` contract as
    ``engine.Engine``) that decodes with draft-model speculation."""

    def __init__(
        self,
        spec: ModelSpec,
        draft_spec: ModelSpec,
        params: Optional[Params] = None,
        draft_params: Optional[Params] = None,
        config: Optional[EngineConfig] = None,
        seed: int = 0,
        speculate_k: int = 4,
        rounds_per_call: int = 4,   # speculative rounds per device
                            # dispatch (lax.scan): the host reads ONE
                            # packed buffer per R rounds instead of per
                            # round — each read is a blocking host
                            # round trip, which at R=1 can outweigh
                            # the round's compute and hide any
                            # speculation win. Host-side stop
                            # detection coarsens to chunk boundaries
                            # (device eos handling stays per-round).
        shard_fn=None,      # target params -> mesh-placed (parallel/sharding)
        kv_sharding=None,   # NamedSharding for the dense [L,B,S,Hkv,Dh]
                            # target caches (ModelShardings.kv); the DRAFT is
                            # always replicated — it is small by design, and
                            # tp-splitting it would trade negligible HBM for
                            # per-layer collectives on the serial propose loop
    ) -> None:
        self.spec = spec.validate()
        self.draft_spec = draft_spec.validate()
        if spec.vocab_size != draft_spec.vocab_size:
            raise ValueError(
                f"draft vocab {draft_spec.vocab_size} != target vocab "
                f"{spec.vocab_size} — speculative decoding needs a shared "
                "token space"
            )
        if speculate_k < 1:
            raise ValueError("speculate_k must be >= 1")
        if rounds_per_call < 1:
            raise ValueError("rounds_per_call must be >= 1")
        self.k = int(speculate_k)
        self.rounds_per_call = int(rounds_per_call)
        self.config = config or EngineConfig()
        if params is None:
            params = init_params(spec, jax.random.key(seed))
        if draft_params is None:
            draft_params = init_params(draft_spec, jax.random.key(seed + 100))
        if shard_fn is not None:
            params = shard_fn(params)
        self._kv_sharding = kv_sharding
        self._rep_sharding = None
        if kv_sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # replicate the draft explicitly on the SAME mesh — leaving it
            # uncommitted would let XLA reshard it per dispatch
            self._rep_sharding = NamedSharding(kv_sharding.mesh,
                                               PartitionSpec())
            draft_params = jax.tree.map(
                lambda x: jax.device_put(x, self._rep_sharding), draft_params)
        from ..ops.quant import fuse_block_weights, prepare_params

        # shared engine-init prep (sharded int4 -> per-tensor "cp"
        # stamps, then fusion); the draft fuses too — its serial propose
        # loop is launch-overhead-bound, exactly what fewer launches
        # helps. The "cp" stamp rides the TARGET's tensors only, so the
        # always-replicated draft keeps the default single-device kernel
        self.params = prepare_params(params)
        self.draft_params = fuse_block_weights(draft_params)
        self._rng = jax.random.key(seed + 1)

        cfg = self.config
        self.batch_buckets = _pow2_buckets(cfg.max_slots)
        self.prefill_buckets = sorted(
            b for b in cfg.prefill_buckets if b <= spec.max_seq_len
        ) or [min(128, spec.max_seq_len)]
        self.seq_buckets = _pow2_buckets(
            min(cfg.max_seq_len, spec.max_seq_len), start=128
        )

        spec_t, spec_d, k = self.spec, self.draft_spec, self.k

        @jax.jit
        def _prefill_both(pt, pd, tokens, seq_lens, sampling, key):
            hid_t, tks, tvs = forward_prefill(spec_t, pt, tokens, seq_lens)
            _hid_d, dks, dvs = forward_prefill(spec_d, pd, tokens, seq_lens)
            b = tokens.shape[0]
            last = hid_t[jnp.arange(b), seq_lens - 1]
            logits = unembed(spec_t, pt, last)
            # first token drawn by the SAME sampler as the other engines
            # (full knob set), packed with its logprob (one blocking read)
            first, lp = sample_tokens_with_logprobs(logits, sampling, key)
            packed = jnp.stack(
                [first, jax.lax.bitcast_convert_type(lp, jnp.int32)])
            return packed, tks, tvs, dks, dvs

        def _round_core(pt, pd, tck, tcv, dck, dcv,
                        lengths, last, active, produced,
                        max_new, eos_ids, sampling, key):
            """One speculative round for every slot. Shapes:
            tck/tcv [L,B,S,..] target cache; dck/dcv draft cache;
            per-slot int32/bool vectors. Returns updated state + emitted
            tokens [B, k+1] (-1 past the accepted run / inactive slots).

            Invariant: both caches hold correct KV for positions
            [0, lengths); ``last`` is the newest token, not yet cached.
            The draft processes every token it proposes, so it needs no
            separate catch-up state — garbage KV from rejected proposals
            sits past ``lengths`` and is masked then overwritten.
            """
            b = lengths.shape[0]
            bidx = jnp.arange(b)
            k_draft, k_resid, k_bonus = jax.random.split(key, 3)
            ones = jnp.ones_like(lengths)

            # --- 1. draft processes `last` -> q_0
            d_logits0, dck, dcv = forward_window(
                spec_d, pd, last[:, None], ones, lengths, dck, dcv
            )
            q_logits = d_logits0[:, 0]                           # [B, V]

            # --- 2. propose k tokens; q_probs collected per step. Both q
            # (here) and p (below) are the knob-MODIFIED distributions —
            # identical masking is what makes the acceptance ratio exact
            # for the request's actual sampling settings.
            greedy = (sampling.temperature <= 0.0)[:, None]

            def propose(carry, step_key):
                dck, dcv, q_logits, pos = carry
                d_tok, probs = draft_sample(q_logits, sampling, greedy,
                                            step_key)
                nxt, dck, dcv = forward_window(
                    spec_d, pd, d_tok[:, None], ones, pos, dck, dcv,
                )
                return (dck, dcv, nxt[:, 0], pos + 1), (d_tok, probs)

            keys = jax.random.split(k_draft, k)
            (dck, dcv, _q_last, _pos), (drafts, q_probs) = jax.lax.scan(
                propose, (dck, dcv, q_logits, lengths + 1), keys
            )
            drafts = drafts.T                                    # [B, k]
            q_probs = jnp.swapaxes(q_probs, 0, 1)                # [B, k, V]

            # --- 3. target verify over [last, d_0..d_{k-1}]
            window_t = jnp.concatenate([last[:, None], drafts], axis=1)
            t_logits, tck, tcv = forward_window(
                spec_t, pt, window_t, jnp.full_like(lengths, k + 1),
                lengths, tck, tcv,
            )                                                    # [B, k+1, V]
            p_probs = masked_sampling_probs(t_logits, sampling)

            # --- 4. acceptance — the shared rejection-sampling rule
            # (engine/spec_accept.py, bit-parity pinned by the r5 parity
            # test); the async verify chunk accepts with the same code
            n_acc, final, _accept = rejection_accept(
                p_probs, q_probs, drafts, greedy, k_resid, k_bonus)

            # --- 5. bookkeeping (inactive slots frozen)
            was_active = active
            slot_pos = jnp.arange(k + 1)[None, :]
            emit_mask = (slot_pos <= n_acc[:, None]) & was_active[:, None]
            emitted = jnp.where(
                emit_mask,
                jnp.concatenate([drafts, jnp.zeros_like(last)[:, None]],
                                axis=1).at[bidx, n_acc].set(final),
                -1,
            )
            n_emit = jnp.where(was_active, n_acc + 1, 0)
            produced = produced + n_emit
            hit_eos = ((emitted == eos_ids[:, None]) &
                       (eos_ids[:, None] >= 0)).any(axis=1)
            done = hit_eos | (produced >= max_new)
            active = was_active & ~done
            lengths = jnp.where(was_active, lengths + n_acc + 1, lengths)
            last = jnp.where(was_active, final, last)
            # untempered model logprob of every emitted token: position j
            # of t_logits is the distribution after window token j, which
            # is exactly what emitted token j was conditioned on (the
            # bonus/residual final at position n_acc included)
            lp_all = jax.nn.log_softmax(t_logits, axis=-1)   # [B, k+1, V]
            lp_emitted = jnp.take_along_axis(
                lp_all, jnp.clip(emitted, 0, None)[:, :, None],
                axis=-1)[..., 0]
            lp_emitted = jnp.where(emitted >= 0, lp_emitted, 0.0)
            # pack emitted + logprob bits + n_acc + active into ONE output
            # buffer: the host makes exactly one blocking read per round
            # (each sync is a full host<->device round trip)
            packed = jnp.concatenate(
                [emitted,
                 jax.lax.bitcast_convert_type(lp_emitted.astype(jnp.float32),
                                              jnp.int32),
                 n_acc[:, None], active.astype(jnp.int32)[:, None]],
                axis=1)
            return (tck, tcv, dck, dcv, lengths, last,
                    active, produced, packed)

        @partial(jax.jit, static_argnames=("rounds",),
                 donate_argnums=(2, 3, 4, 5))
        def _rounds(pt, pd, tck, tcv, dck, dcv, lengths, last, active,
                    produced, max_new, eos_ids, sampling, key,
                    rounds: int):
            """``rounds`` speculative rounds in ONE dispatch; the host
            reads one stacked packed buffer per call. Slots that finish
            mid-chunk stay frozen for the remaining rounds (emitted=-1);
            once EVERY slot froze, the remaining rounds skip entirely
            (``lax.cond`` on a scalar pred runs one branch on TPU), so an
            overshooting chunk streams no weights — that makes the
            one-ahead optimistic dispatch in ``generate`` nearly free."""

            def body(carry, kr):
                def run(c):
                    (tck, tcv, dck, dcv, lengths, last, active,
                     produced) = c
                    return _round_core(
                        pt, pd, tck, tcv, dck, dcv, lengths, last, active,
                        produced, max_new, eos_ids, sampling, kr)

                def skip(c):
                    b = c[4].shape[0]
                    packed = jnp.concatenate(
                        [jnp.full((b, k + 1), -1, jnp.int32),
                         jnp.zeros((b, k + 1), jnp.int32),
                         jnp.zeros((b, 2), jnp.int32)], axis=1)
                    return (*c, packed)

                *state, packed = jax.lax.cond(
                    jnp.any(carry[6]), run, skip, carry)
                return tuple(state), packed

            carry, packs = jax.lax.scan(
                body, (tck, tcv, dck, dcv, lengths, last, active, produced),
                jax.random.split(key, rounds))
            return carry, packs                      # [R, B, 2(k+1)+2]

        self._prefill_both = _prefill_both
        self._rounds = _rounds

        # metrics
        self.prefill_stats = LatencyStats()
        self.round_stats = LatencyStats()
        cap = int(getattr(config, "timeline_capacity", 4096) or 0)
        self.timeline: Optional[StepTimeline] = (
            StepTimeline(capacity=cap, name="speculative") if cap else None)
        self._total_requests = 0
        self._total_prompt_tokens = 0
        self._total_generated = 0
        self._total_rounds = 0
        self._total_accepted = 0
        self._total_proposed = 0

    # ------------------------------------------------------------ generate

    @hot_path
    def generate(self, requests: List[GenerationRequest]) -> List[GenerationResult]:
        if not requests:
            return []
        if min(len(r.prompt) for r in requests) < 1:
            raise ValueError("empty prompt")
        self._total_requests += len(requests)
        n = len(requests)
        bb = _next_bucket(n, self.batch_buckets)
        max_prompt = min(max(len(r.prompt) for r in requests),
                         max(self.prefill_buckets))
        tb = _next_bucket(max_prompt, self.prefill_buckets)
        max_new = max(r.max_new_tokens for r in requests)
        total_cap = max(tb + self.k + 1, _next_bucket(
            min(max_prompt + max_new + self.k + 1, self.seq_buckets[-1]),
            self.seq_buckets,
        ))

        tokens = np.zeros((bb, tb), dtype=np.int32)
        seq_lens = np.ones((bb,), dtype=np.int32)
        max_new_arr = np.zeros((bb,), dtype=np.int32)
        eos = np.full((bb,), -1, dtype=np.int32)
        temps = np.zeros((bb,), dtype=np.float32)
        top_k = np.zeros((bb,), dtype=np.int32)
        top_p = np.ones((bb,), dtype=np.float32)
        min_p = np.zeros((bb,), dtype=np.float32)
        for i, r in enumerate(requests):
            p = r.prompt[-tb:]
            tokens[i, : len(p)] = p
            seq_lens[i] = len(p)
            max_new_arr[i] = max(1, min(r.max_new_tokens,
                                        total_cap - len(p) - self.k - 1))
            eos[i] = r.eos_id
            temps[i] = r.temperature
            top_k[i] = r.top_k
            top_p[i] = r.top_p
            min_p[i] = r.min_p
        sampling = SamplingParams(
            jnp.asarray(temps), jnp.asarray(top_k), jnp.asarray(top_p),
            jnp.asarray(min_p),
        )

        sp = host_span(self.timeline, "engine.prefill.dispatch",
                       dispatch=True, rows=n, spec=True)
        t0 = sp.t0
        self._rng, k0 = jax.random.split(self._rng)
        first_dev, tks, tvs, dks, dvs = self._prefill_both(
            self.params, self.draft_params,
            jnp.asarray(tokens), jnp.asarray(seq_lens),
            sampling, k0,
        )
        # graftlint: ok[host-sync-hot-path] ONE first-token read per batch prefill (TTFT emission point)
        fp = np.asarray(first_dev)                  # [2, bb]: tokens; lp bits
        first = fp[0]
        first_lp = fp[1].view(np.float32)

        L_t = self.spec.n_layers
        L_d = self.draft_spec.n_layers
        dt = jnp.dtype(self.config.kv_dtype)
        shape_t = (L_t, bb, total_cap, self.spec.n_kv_heads,
                   self.spec.head_dim)
        shape_d = (L_d, bb, total_cap, self.draft_spec.n_kv_heads,
                   self.draft_spec.head_dim)
        # target caches follow the tp/kv sharding (with per-axis fallback
        # for bucket dims that don't divide the mesh); draft caches
        # replicate with their (replicated) params
        tdev = {}
        if self._kv_sharding is not None:
            from ..parallel.sharding import compatible_sharding

            tdev = {"device": compatible_sharding(self._kv_sharding,
                                                  shape_t)}
        ddev = {"device": self._rep_sharding} if self._rep_sharding else {}
        tck = jnp.zeros(shape_t, dt, **tdev).at[:, :, :tb].set(tks.astype(dt))
        tcv = jnp.zeros(shape_t, dt, **tdev).at[:, :, :tb].set(tvs.astype(dt))
        dck = jnp.zeros(shape_d, dt, **ddev).at[:, :, :tb].set(dks.astype(dt))
        dcv = jnp.zeros(shape_d, dt, **ddev).at[:, :, :tb].set(dvs.astype(dt))

        is_real = np.zeros((bb,), bool)
        is_real[:n] = True
        produced_np = is_real.astype(np.int32)
        hit = is_real & (first == eos) & (eos >= 0)
        active_np = is_real & ~hit & (produced_np < max_new_arr)
        out_tokens: List[List[int]] = [[int(first[i])] for i in range(n)]
        out_lps: List[List[float]] = [[float(first_lp[i])] for i in range(n)]
        ttft = sp.close(prefill_tokens=int(sum(seq_lens[:n])),
                        program=("spec_prefill", bb, tb)) - t0
        self.prefill_stats.add(ttft)

        lengths = jnp.asarray(seq_lens)
        last = jnp.asarray(np.where(first >= 0, first, 0).astype(np.int32))
        active = jnp.asarray(active_np)
        produced = jnp.asarray(produced_np)
        max_new_j = jnp.asarray(max_new_arr)
        eos_j = jnp.asarray(eos)

        sp = host_span(self.timeline, "engine.verify.dispatch",
                       dispatch=True, rows=n,
                       rounds_per_call=self.rounds_per_call, k=self.k)
        t1 = sp.t0
        act_host = active_np
        scanned = [0] * n        # host-stop scan resume offsets
        # the prefill-sampled FIRST token can itself match stop_ids/
        # stop_sequences (ADVICE r2): scan before the loop so such a
        # request never burns a target+draft round
        stopped_rows = scan_host_stops(out_tokens, requests, act_host,
                                       scanned)
        if stopped_rows and act_host.any():
            active = active.at[
                jnp.asarray(stopped_rows, jnp.int32)].set(False)
        R = self.rounds_per_call
        # host-side stop detection must land on device state between
        # chunks, so such requests keep the sync dispatch→read loop;
        # everything else runs one chunk AHEAD (dispatch i+1, then read
        # i): the packed read — a blocking host round trip —
        # overlaps the next chunk's execution, and a chunk dispatched
        # past the end all-skips on device (``_rounds``)
        overlap = not any(r.stop_ids or r.stop_sequences
                          for r in requests)
        state = (tck, tcv, dck, dcv, lengths, last, active, produced)
        del tck, tcv, dck, dcv, active
        pending = None
        while act_host.any():
            if pending is None:
                self._rng, kr = jax.random.split(self._rng)
                state, packs = self._rounds(
                    self.params, self.draft_params, *state,
                    max_new_j, eos_j, sampling, kr, rounds=R,
                )
            else:
                state, packs = pending
                pending = None
            if overlap:
                self._rng, kr = jax.random.split(self._rng)
                pending = self._rounds(
                    self.params, self.draft_params, *state,
                    max_new_j, eos_j, sampling, kr, rounds=R,
                )
            # graftlint: ok[host-sync-hot-path] ONE blocking read per R speculative rounds (up to R*(k+1) tokens amortize it)
            pks = np.asarray(packs)     # ONE blocking read per R rounds
            k1 = self.k + 1
            for r in range(R):
                pk = pks[r]
                em = pk[:, :k1]
                lps = np.ascontiguousarray(
                    pk[:, k1: 2 * k1]).view(np.float32)
                n_acc_np = pk[:, 2 * k1]
                act_host = pk[:, 2 * k1 + 1].astype(bool)
                live = int((em[:, 0] >= 0).sum())
                if not live:
                    continue            # chunk tail after all slots froze
                self._total_rounds += 1
                self._total_accepted += int(n_acc_np[em[:, 0] >= 0].sum())
                self._total_proposed += self.k * live
                for i in range(n):
                    for j in range(k1):
                        if em[i, j] >= 0:
                            out_tokens[i].append(int(em[i, j]))
                            out_lps[i].append(float(lps[i, j]))
            # early exit on host-side stops (ADVICE r1), now at CHUNK
            # granularity: the device rounds only know eos_id — a matched
            # stop_ids/stop_sequences request can overshoot by up to R
            # rounds (trimmed post-hoc) but no longer burns to
            # max_new_tokens
            stopped_rows = scan_host_stops(out_tokens, requests, act_host,
                                           scanned)
            if stopped_rows and act_host.any():
                # sync path only (``overlap`` is off for such requests)
                state = state[:6] + (
                    state[6].at[jnp.asarray(stopped_rows,
                                            jnp.int32)].set(False),
                    state[7])
        decode_t = sp.close(program=("spec_rounds", bb, R)) - t1
        self.round_stats.add(decode_t)

        results = []
        for i, r in enumerate(requests):
            toks, stopped = trim_at_stops(out_tokens[i], r)
            self._total_prompt_tokens += len(r.prompt)
            self._total_generated += len(toks)
            results.append(GenerationResult(
                request_id=r.request_id or f"spec-{self._total_requests}-{i}",
                tokens=toks,
                logprobs=out_lps[i][: len(toks)],
                finish_reason="stop" if stopped else "length",
                prompt_tokens=len(r.prompt),
                ttft_s=ttft,
                decode_s=decode_t,
            ))
        return results

    # ------------------------------------------------------------- warmup

    def warmup(self, batch: Optional[int] = None,
               max_new_tokens: int = 2) -> int:
        """Pre-compile prefill + speculative rounds per (batch bucket ×
        prefill bucket); the prompt is clamped so at least one speculative
        round actually runs (see ``Engine.warmup``). Returns the number of
        warmup generates run."""
        sizes = [batch] if batch else self.batch_buckets
        cap = self.seq_buckets[-1] - self.k - 1 - max_new_tokens
        runs = 0
        for n in sizes:
            for tb in self.prefill_buckets:
                plen = max(1, min(tb, cap))
                self.generate([
                    GenerationRequest(prompt=[1] * plen,
                                      max_new_tokens=max_new_tokens)
                    for _ in range(n)
                ])
                runs += 1
        return runs

    # ------------------------------------------------------------ metrics

    def get_metrics(self) -> Dict[str, Any]:
        acc_rate = (self._total_accepted / self._total_proposed
                    if self._total_proposed else 0.0)
        return {
            "total_requests": self._total_requests,
            "total_prompt_tokens": self._total_prompt_tokens,
            "total_generated_tokens": self._total_generated,
            "speculate_k": self.k,
            "rounds": self._total_rounds,
            "draft_acceptance_rate": acc_rate,
            "tokens_per_round": ((self._total_accepted + self._total_rounds)
                                 / self._total_rounds
                                 if self._total_rounds else 0.0),
            "prefill": self.prefill_stats.snapshot(),
            "decode": self.round_stats.snapshot(),
        }
