"""The inference engine: jit-compiled prefill + chunked decode on TPU.

This replaces the reference's mock inference core — ``FakeModel.predict``'s
50–150 ms ``asyncio.sleep`` (``src/mock_models/fake_model.py:47``) — with a
real XLA program, and is the component every host-side layer (worker, batcher,
coordinator) ultimately dispatches into (the ``[HOT]`` line of SURVEY.md §3.1).

Execution model (SURVEY.md §7 hard-part #1 — static shapes vs dynamic
serving):

- **Prefill** runs on (batch-bucket, seq-bucket) padded shapes; prompts are
  right-padded, lengths carried as data. One compiled program per bucket
  pair, reused forever after.
- **Decode** is a ``lax.scan`` over ``decode_steps_per_call`` steps, entirely
  on device: forward, sample, advance lengths, write KV — no host round-trip
  per token. The host syncs once per chunk to test "is anyone still active",
  amortizing the device→host latency over the chunk.
- **Sampling knobs are data** (``SamplingParams`` arrays), so greedy and
  nucleus requests share one compiled program.
- **KV buffers are donated** into the decode chunk, so XLA mutates the HBM
  cache in place instead of double-buffering ~GBs per step.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import EngineConfig
from ..models.base import (
    ModelSpec,
    Params,
    forward_decode,
    init_params,
    unembed,
)
from ..ops.sampling import (
    SamplingParams,
    sample_tokens,
    sample_tokens_with_logprobs,
)
from ..obs.timeline import StepTimeline, host_span
from ..utils.hotpath import hot_path
from ..utils.tracing import LatencyStats
from .types import (  # noqa: F401  (re-export)
    GenerationRequest,
    GenerationResult,
    scan_host_stops,
    trim_at_stops,
)


def _next_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in sorted(buckets):
        if n <= b:
            return b
    raise ValueError(f"size {n} exceeds largest bucket {max(buckets)}")


def _check_same_mesh(params, sp_mesh) -> None:
    """The params' placement and sp_mesh must agree: params on one mesh
    with activations constrained to another makes XLA reshard the whole
    model across device orderings inside every prefill. Covers both
    construction paths — a shard_fn and pre-sharded params passed
    directly; no-op when params carry no mesh."""
    leaf = jax.tree.leaves(params)[0]
    mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
    if mesh is not None and mesh != sp_mesh:
        raise ValueError(
            "params are placed on a different mesh than sp_mesh (via "
            "shard_fn or pre-sharded) — cross-mesh prefill would reshard "
            "params every dispatch; build both from the same Mesh")


def _pow2_buckets(cap: int, start: int = 1) -> List[int]:
    out, b = [], start
    while b < cap:
        out.append(b)
        b *= 2
    out.append(cap)
    return out


class Engine:
    """Single-program inference engine over one model.

    ``generate`` is synchronous device code; async callers (worker RPC,
    batcher backend) wrap it in an executor thread. Mesh/sharding-aware
    construction is layered in ``parallel/`` — the engine itself only sees
    (possibly sharded) params and arrays.
    """

    def __init__(
        self,
        spec: ModelSpec,
        params: Optional[Params] = None,
        config: Optional[EngineConfig] = None,
        seed: int = 0,
        shard_fn=None,   # optional: fn(params) -> sharded params (parallel/)
        sp_mesh=None,    # optional: mesh with a real sp axis — long prompts
                         # prefill sequence-parallel via ring attention
                         # (parallel/long_context.py) AND decode runs
                         # context-parallel against a sequence-sharded KV
                         # cache (greedy near-ties may resolve differently
                         # than unsharded: reordered fp reductions)
        artifact_path: Optional[str] = None,   # pre-fused serving artifact
                         # (engine/artifact.py): restore the prepared tree
                         # instead of init/quantize/fuse/pad; spec may be
                         # None (the artifact's sidecar is authoritative)
        artifact_selfcheck: bool = True,       # replay the golden-token
                         # probe before admitting traffic (mismatch raises
                         # ArtifactCorruptError — never serve wrong numerics)
    ) -> None:
        self.artifact_manifest: Optional[Dict[str, Any]] = None
        if artifact_path is not None:
            from .artifact import load_artifact

            a_spec, params, self.artifact_manifest = load_artifact(
                artifact_path)
            if spec is None:
                spec = a_spec
        self.spec = spec.validate()
        self.config = config or EngineConfig()
        if params is None:
            params = init_params(spec, jax.random.key(seed))
        if shard_fn is not None:
            params = shard_fn(params)
        if self.artifact_manifest is not None:
            # the artifact IS the post-prepare tree — re-preparing would
            # re-pay the fuse/pad cost the fast path exists to skip
            # (prepare_params is idempotent, but not free)
            self.params = params
        else:
            from ..ops.quant import prepare_params

            # kernel-mode selection (sharded int4 -> "cp") + qkv/gate+up
            # payload fusion, shared across engines (ops.quant.prepare_params)
            self.params = prepare_params(params)
        self._rng = jax.random.key(seed + 1)

        # context-parallel decode: with an sp mesh the dense KV cache is
        # PLACED sequence-sharded (parallel.sharding.kv_cache_pspec) and
        # stays that way through the decode scan — each chip holds and
        # reads 1/sp of the cache; GSPMD inserts the softmax/contraction
        # all-reduces. Applied per batch when the bucket dims divide the
        # axes (see generate()).
        self._cache_sharding = None
        if sp_mesh is not None:
            from jax.sharding import NamedSharding

            from ..parallel.sharding import kv_cache_pspec

            self._cache_sharding = NamedSharding(sp_mesh, kv_cache_pspec())

        cfg = self.config
        self.batch_buckets = _pow2_buckets(cfg.max_slots)
        self.prefill_buckets = sorted(
            b for b in cfg.prefill_buckets if b <= spec.max_seq_len
        ) or [min(128, spec.max_seq_len)]
        self.seq_buckets = _pow2_buckets(
            min(cfg.max_seq_len, spec.max_seq_len), start=128
        )

        # ---- jitted programs (compiled per bucket shape, cached by jax)
        spec_ = self.spec
        from ..parallel.long_context import prefill_fn_for

        if sp_mesh is not None:
            # no-op when params carry no mesh — covers pre-sharded
            # params passed without a shard_fn too
            _check_same_mesh(self.params, sp_mesh)
        fwd_prefill = prefill_fn_for(spec_, sp_mesh, self.prefill_buckets)

        @jax.jit
        def _prefill(params, tokens, seq_lens, sampling, key):
            hidden, ks, vs = fwd_prefill(spec_, params, tokens, seq_lens)
            b = tokens.shape[0]
            last = hidden[jnp.arange(b), seq_lens - 1]        # [B, D]
            logits = unembed(spec_, params, last)             # [B, V] fp32
            # sample INSIDE the program: an eager sample after prefill is
            # a chain of separate device dispatches, each adding its
            # launch latency to TTFT. Token + its logprob pack into one
            # [2, B] int32 buffer (logprob bitcast) = one blocking read.
            first, lp = sample_tokens_with_logprobs(logits, sampling, key)
            packed = jnp.stack(
                [first, jax.lax.bitcast_convert_type(lp, jnp.int32)])
            return packed, ks, vs

        @partial(jax.jit, static_argnames=("n_steps",), donate_argnums=(1, 2, 3, 4, 5, 6))
        def _decode_chunk(
            params, ck, cv, lengths, last_tokens, active, produced,
            max_new, sampling, eos_ids, key, n_steps: int,
        ):
            """n_steps of decode for every slot, fully on device.

            Shapes: ck/cv [L,B,S,Hkv,Dh]; lengths/last_tokens/active/produced/
            max_new/eos_ids [B]. Emits tokens [n_steps, B] (-1 for inactive).
            """

            def step(carry, step_key):
                ck, cv, lengths, last, active, produced = carry
                hidden, ck, cv = forward_decode(
                    spec_, params, last, lengths, ck, cv
                )
                logits = unembed(spec_, params, hidden)        # [B, V]
                next_tok, lp = sample_tokens_with_logprobs(
                    logits, sampling, step_key)
                was_active = active
                produced = produced + was_active.astype(jnp.int32)
                hit_eos = (next_tok == eos_ids) & (eos_ids >= 0)
                done = hit_eos | (produced >= max_new)
                active = was_active & ~done
                lengths = lengths + was_active.astype(jnp.int32)
                last = jnp.where(was_active, next_tok, last)
                emitted = jnp.where(was_active, next_tok, -1)
                lp = jnp.where(was_active, lp, 0.0)
                return (ck, cv, lengths, last, active, produced), (emitted, lp)

            keys = jax.random.split(key, n_steps)
            carry, (toks, lps) = jax.lax.scan(
                step, (ck, cv, lengths, last_tokens, active, produced), keys
            )
            # pack emitted tokens + their logprobs (bitcast) + live flags
            # into ONE buffer: the host then makes exactly one blocking
            # read per chunk (each sync is a full host<->device round
            # trip).
            packed = jnp.concatenate(
                [toks, jax.lax.bitcast_convert_type(lps, jnp.int32),
                 carry[4][None].astype(jnp.int32)], axis=0)
            return carry, packed

        self._prefill = _prefill
        self._decode_chunk = _decode_chunk

        # ---- metrics
        self.prefill_stats = LatencyStats()
        self.decode_stats = LatencyStats()
        cap = int(getattr(config, "timeline_capacity", 4096) or 0)
        self.timeline: Optional[StepTimeline] = (
            StepTimeline(capacity=cap, name="static") if cap else None)
        self._total_requests = 0
        self._total_prompt_tokens = 0
        self._total_generated_tokens = 0
        self._total_errors = 0

        if self.artifact_manifest is not None and artifact_selfcheck:
            # golden-token self-check BEFORE any traffic: replays the
            # save-time probe against the restored tree through the real
            # compiled programs (also a bb=1 warmup). Raises
            # ArtifactCorruptError on divergence — the factory falls back
            # to the slow path rather than serve wrong numerics.
            from .artifact import verify_golden

            verify_golden(self, self.artifact_manifest)

    # ------------------------------------------------------------ generate

    @hot_path
    def generate(self, requests: List[GenerationRequest]) -> List[GenerationResult]:
        """Run a batch of generation jobs to completion. Static-shape safe:
        pads batch and sequence dims to buckets so repeat calls hit the jit
        cache."""
        if not requests:
            return []
        self._total_requests += len(requests)
        n = len(requests)
        bb = _next_bucket(n, self.batch_buckets)
        max_prompt = max(len(r.prompt) for r in requests)
        if min(len(r.prompt) for r in requests) < 1:
            raise ValueError("empty prompt")
        # overlong prompts keep their tail (sliding-window truncation)
        max_prompt = min(max_prompt, max(self.prefill_buckets))
        tb = _next_bucket(max_prompt, self.prefill_buckets)
        max_new = max(r.max_new_tokens for r in requests)
        total_cap = max(tb, _next_bucket(
            min(max_prompt + max_new, self.seq_buckets[-1]), self.seq_buckets
        ))

        # ---- host-side batch assembly (numpy, then one transfer)
        tokens = np.zeros((bb, tb), dtype=np.int32)
        seq_lens = np.ones((bb,), dtype=np.int32)      # padded rows: len 1
        max_new_arr = np.zeros((bb,), dtype=np.int32)
        eos = np.full((bb,), -1, dtype=np.int32)
        temps = np.zeros((bb,), dtype=np.float32)
        top_k = np.zeros((bb,), dtype=np.int32)
        top_p = np.ones((bb,), dtype=np.float32)
        min_p = np.zeros((bb,), dtype=np.float32)
        for i, r in enumerate(requests):
            p = r.prompt[-tb:]                          # clamp overlong prompts
            tokens[i, : len(p)] = p
            seq_lens[i] = len(p)
            max_new_arr[i] = max(1, min(r.max_new_tokens, total_cap - len(p)))
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
                       dispatch=True, rows=n)
        t0 = sp.t0
        self._rng, k0 = jax.random.split(self._rng)
        first_packed, ks, vs = self._prefill(
            self.params, jnp.asarray(tokens), jnp.asarray(seq_lens),
            sampling, k0,
        )

        # ---- seed decode state; KV cache sized to the total-seq bucket.
        # With an sp mesh the cache is born sequence-sharded (decode then
        # runs context-parallel); small buckets that don't divide the mesh
        # axes fall back to the default placement
        L, Hkv, Dh = self.spec.n_layers, self.spec.n_kv_heads, self.spec.head_dim
        dt = jnp.dtype(self.config.kv_dtype)
        dev = {}
        if self._cache_sharding is not None:
            from ..parallel.sharding import compatible_sharding

            # per-axis fallback: bb=1 can't split over dp, but that must
            # not cost the sequence split
            dev = {"device": compatible_sharding(
                self._cache_sharding, (L, bb, total_cap, Hkv, Dh))}
        ck = jnp.zeros((L, bb, total_cap, Hkv, Dh), dtype=dt, **dev)
        cv = jnp.zeros((L, bb, total_cap, Hkv, Dh), dtype=dt, **dev)
        ck = ck.at[:, :, :tb].set(ks.astype(dt))
        cv = cv.at[:, :, :tb].set(vs.astype(dt))

        lengths = jnp.asarray(seq_lens)
        is_real = np.zeros((bb,), dtype=bool)
        is_real[:n] = True
        # graftlint: ok[host-sync-hot-path] ONE packed first-token read per generate() batch
        first_packed_np = np.asarray(first_packed)      # ONE blocking read
        first_np = first_packed_np[0]
        first_lp_np = first_packed_np[1].view(np.float32)
        produced_np = is_real.astype(np.int32)          # the prefill sample
        hit = is_real & (first_np == eos) & (eos >= 0)
        active_np = is_real & ~hit & (produced_np < max_new_arr)
        first_np = np.where(is_real, first_np, -1)

        ttft = sp.close(prefill_tokens=int(seq_lens[:n].sum()),
                        program=("prefill", bb, tb)) - t0
        self.prefill_stats.add(ttft)

        out_tokens: List[List[int]] = [[int(first_np[i])] for i in range(n)]
        out_lps: List[List[float]] = [[float(first_lp_np[i])]
                                      for i in range(n)]

        active = jnp.asarray(active_np)
        produced = jnp.asarray(produced_np)
        last = jnp.asarray(np.where(first_np >= 0, first_np, 0).astype(np.int32))
        max_new_j = jnp.asarray(max_new_arr)
        eos_j = jnp.asarray(eos)

        sp = host_span(self.timeline, "engine.decode.dispatch",
                       dispatch=True, rows=n)
        t1 = sp.t0
        n_steps = self.config.decode_steps_per_call
        # loop condition runs on the HOST mirror of the active flags (seeded
        # from the prefill sample, updated from each chunk's packed row) —
        # a device-side active.any() would cost one extra round trip per
        # chunk
        act_host = active_np
        scanned = [0] * n        # host-stop scan resume offsets
        # the prefill-sampled FIRST token can itself match stop_ids/
        # stop_sequences (ADVICE r2): scan before the loop so such a
        # request never burns a full decode chunk
        stopped_rows = scan_host_stops(out_tokens, requests, act_host,
                                       scanned)
        if stopped_rows and act_host.any():
            active = active.at[
                jnp.asarray(stopped_rows, jnp.int32)].set(False)
        while act_host.any():
            self._rng, kc = jax.random.split(self._rng)
            (ck, cv, lengths, last, active, produced), packed = self._decode_chunk(
                self.params, ck, cv, lengths, last, active, produced,
                max_new_j, sampling, eos_j, kc, n_steps=n_steps,
            )
            # graftlint: ok[host-sync-hot-path] THE designed sync point: ONE packed read per n_steps-token decode chunk
            packed_np = np.asarray(packed)   # ONE blocking read per chunk
            toks_np = packed_np[:n_steps]               # [n_steps, bb]
            lps_np = packed_np[n_steps:2 * n_steps].view(np.float32)
            act_host = packed_np[-1].astype(bool)
            for i in range(n):
                for s in range(n_steps):
                    t = int(toks_np[s, i])
                    if t >= 0:
                        out_tokens[i].append(t)
                        out_lps[i].append(float(lps_np[s, i]))
            # early exit on host-side stops (ADVICE r1): the device loop
            # only knows eos_id, so a request whose stop_ids/stop_sequences
            # matched would otherwise burn decode chunks to max_new_tokens
            # and be trimmed after the fact. One batched flag clear —
            # skipped when the loop is exiting anyway.
            stopped_rows = scan_host_stops(out_tokens, requests, act_host,
                                           scanned)
            if stopped_rows and act_host.any():
                active = active.at[
                    jnp.asarray(stopped_rows, jnp.int32)].set(False)
        decode_t = sp.close(n_steps=n_steps, program=("decode", bb, n_steps)) - t1
        self.decode_stats.add(decode_t)

        results = []
        for i, r in enumerate(requests):
            toks, stopped = trim_at_stops(out_tokens[i], r)
            lps = out_lps[i][: len(toks)]
            self._total_prompt_tokens += len(r.prompt)
            self._total_generated_tokens += len(toks)
            results.append(
                GenerationResult(
                    request_id=r.request_id or f"gen-{self._total_requests}-{i}",
                    tokens=toks,
                    finish_reason="stop" if stopped else "length",
                    prompt_tokens=len(r.prompt),
                    logprobs=lps,
                    ttft_s=ttft,
                    decode_s=decode_t,
                )
            )
        return results

    # ------------------------------------------------------------- warmup

    def warmup(self, batch: Optional[int] = None,
               max_new_tokens: int = 2) -> int:
        """Pre-compile the serving programs by running one tiny generate
        per (batch bucket × prefill bucket) — EVERY batch bucket by
        default, because the first real request is typically a single one
        (bb=1) and warming only the largest bucket would leave exactly
        that shape cold. Because total-cap buckets round up, a warmup with
        small ``max_new_tokens`` usually lands in the same decode-chunk
        shape moderate generations use; the prompt is clamped below the
        top sequence bucket so at least one decode chunk actually runs.
        Stat counters do tick (warmup IS traffic). Returns the number of
        warmup generates run."""
        sizes = [batch] if batch else self.batch_buckets
        runs = 0
        for n in sizes:
            for tb in self.prefill_buckets:
                plen = max(1, min(tb, self.seq_buckets[-1] - max_new_tokens))
                self.generate([
                    GenerationRequest(prompt=[1] * plen,
                                      max_new_tokens=max_new_tokens)
                    for _ in range(n)
                ])
                runs += 1
        return runs

    def warmup_from_manifest(self, max_new_tokens: int = 2) -> int:
        """Artifact-aware warmup: compile only the batch buckets the
        artifact's writer recorded as its serving shapes, so a respawned
        worker warms what its predecessor actually served instead of the
        full bucket grid. Falls back to the full ``warmup`` when the
        manifest records nothing usable (absent, or config drifted)."""
        b = (self.artifact_manifest or {}).get("buckets", {})
        batches = [n for n in b.get("batch", []) if n in self.batch_buckets]
        if not batches:
            return self.warmup(max_new_tokens=max_new_tokens)
        return sum(self.warmup(batch=n, max_new_tokens=max_new_tokens)
                   for n in batches)

    # ------------------------------------------------------------- metrics

    def get_metrics(self) -> Dict[str, Any]:
        """Every component exposes get_stats/get_metrics (SURVEY.md §5)."""
        return {
            "total_requests": self._total_requests,
            "total_prompt_tokens": self._total_prompt_tokens,
            "total_generated_tokens": self._total_generated_tokens,
            "total_errors": self._total_errors,
            "prefill": self.prefill_stats.snapshot(),
            "decode": self.decode_stats.snapshot(),
            "spec": {
                "n_layers": self.spec.n_layers,
                "d_model": self.spec.d_model,
                "vocab_size": self.spec.vocab_size,
            },
        }
