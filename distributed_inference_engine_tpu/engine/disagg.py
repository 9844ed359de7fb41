"""Disaggregated prefill/decode: prefill-only engine + KV handoff format.

The reference *declares* "disaggregated inference" — a preproc/decode split
that never got code (``/root/reference/README.md:15,96-98``; SURVEY.md §2.3
last row). This module is the TPU-native realisation (BASELINE.json
configs[4]): a **prefill pool** computes each prompt's KV state and first
token on its own chips, then hands the KV off over DCN to a **decode pool**
whose slots only ever run the memory-bound decode loop. Prefill's
compute-bound batched matmuls and decode's latency-sensitive small steps stop
interfering (SURVEY.md §7 hard-part #3 — disaggregation is the escape
hatch).

Split of responsibilities:

- ``PrefillEngine`` (this file): bucketed batch prefill → per-request
  ``PrefillHandoff`` (first sampled token + prompt KV, trimmed to the true
  prompt length, in the decode pool's KV dtype).
- ``ContinuousEngine.submit_prefilled``: admits a handoff into a paged slot
  — scatters the KV into pages and resumes decoding as if it had prefetched
  the prompt itself.
- Wire form (``handoff_to_wire``/``handoff_from_wire``): raw little-endian
  bytes + dtype/shape metadata, carried inside the framed RPC's msgpack
  payload (``utils/framing.py``). The host RPC plane is the DCN transport;
  tensor traffic *within* a pool stays XLA collectives (SURVEY.md §2.4).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import EngineConfig
from ..models.base import (
    ModelSpec,
    Params,
    init_params,
    unembed,
)
from ..ops.sampling import (
    SamplingParams,
    sample_tokens_with_logprobs,
)
from ..utils.hotpath import hot_path
from ..utils.tracing import LatencyStats
from .engine import _next_bucket, _pow2_buckets
from .types import GenerationRequest


@dataclasses.dataclass
class PrefillHandoff:
    """Everything a decode worker needs to resume a prefilled sequence.

    ``k``/``v`` are ``[L, T - kv_start, Hkv, Dh]`` numpy arrays in the
    KV-cache dtype: positions ``[kv_start, prompt_len)`` of the prompt
    (``kv_start`` is 0 for a full handoff — the common case). A nonzero
    ``kv_start`` is the prefix-aware delta handoff: the sender probed the
    decode pool's prefix cache (``WorkerServer._rpc_prefix_probe``) and
    omitted the page-aligned head the pool already holds. ``first_token``
    was sampled from the prefill logits with the request's own sampling
    params, so the decode side starts at position T with ``produced == 1``.
    """

    request_id: str
    prompt_len: int
    first_token: int
    k: np.ndarray
    v: np.ndarray
    first_logprob: float = 0.0       # untempered log p of first_token
    kv_start: int = 0                # prompt positions [0, kv_start) omitted

    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes


def trim_handoff(h: PrefillHandoff, kv_start: int) -> PrefillHandoff:
    """Delta form of ``h``: drop the KV for positions < ``kv_start`` (which
    the receiver's prefix cache already holds). No-op for kv_start <= 0."""
    if kv_start <= 0:
        return h
    if not 0 < kv_start < h.prompt_len:
        raise ValueError(
            f"kv_start {kv_start} out of range for prompt_len {h.prompt_len}")
    if h.kv_start:
        raise ValueError("handoff is already trimmed")
    return dataclasses.replace(
        h, k=h.k[:, kv_start:], v=h.v[:, kv_start:], kv_start=kv_start)


def handoff_to_wire(h: PrefillHandoff) -> Dict[str, Any]:
    """Marshal for the framed RPC plane (msgpack carries bytes natively)."""
    return {
        "request_id": h.request_id,
        "prompt_len": h.prompt_len,
        "first_token": h.first_token,
        "first_logprob": h.first_logprob,
        "kv_start": h.kv_start,
        "dtype": jnp.dtype(h.k.dtype).name,
        "shape": list(h.k.shape),
        "k": h.k.tobytes(),
        "v": h.v.tobytes(),
    }


def handoff_from_wire(d: Dict[str, Any]) -> PrefillHandoff:
    dtype = jnp.dtype(d["dtype"])           # resolves bfloat16 via ml_dtypes
    shape = tuple(int(s) for s in d["shape"])

    def _arr(b: Any) -> np.ndarray:
        if isinstance(b, str):              # JSON-codec fallback: base64
            import base64

            b = base64.b64decode(b)
        return np.frombuffer(b, dtype=dtype).reshape(shape)

    return PrefillHandoff(
        request_id=str(d["request_id"]),
        prompt_len=int(d["prompt_len"]),
        first_token=int(d["first_token"]),
        first_logprob=float(d.get("first_logprob", 0.0)),
        kv_start=int(d.get("kv_start", 0)),
        k=_arr(d["k"]),
        v=_arr(d["v"]),
    )


class PrefillEngine:
    """Prefill-only engine for the prefill pool of a disaggregated pair.

    Same bucketed batch assembly as ``Engine.generate`` (one compiled
    program per (batch, seq) bucket pair), but stops after the first sampled
    token: instead of seeding a decode loop it exports each request's KV
    state as a ``PrefillHandoff``.
    """

    def __init__(
        self,
        spec: ModelSpec,
        params: Optional[Params] = None,
        config: Optional[EngineConfig] = None,
        seed: int = 0,
        shard_fn=None,
        sp_mesh=None,    # optional: sequence-parallel ring-attention
                         # prefill (parallel/long_context.py) — the natural
                         # fit for a long-prompt prefill pool
    ) -> None:
        self.spec = spec.validate()
        self.config = config or EngineConfig()
        if params is None:
            params = init_params(spec, jax.random.key(seed))
        if shard_fn is not None:
            params = shard_fn(params)
        self.params = params
        self._rng = jax.random.key(seed + 1)

        cfg = self.config
        self.batch_buckets = _pow2_buckets(cfg.max_slots)
        # bucket rule must MATCH the decode pool's (ContinuousEngine): top
        # bucket is max_seq itself, so a prompt the decode pool would admit
        # is never silently truncated here (they share EngineConfig on a
        # disaggregated deploy)
        self.max_seq_len = min(cfg.max_seq_len, spec.max_seq_len)
        self.prefill_buckets = sorted(
            {b for b in cfg.prefill_buckets if b < self.max_seq_len}
            | {self.max_seq_len}
        )
        self.kv_dtype = jnp.dtype(cfg.kv_dtype)

        spec_ = self.spec
        from ..parallel.long_context import prefill_fn_for
        from .engine import _check_same_mesh

        if sp_mesh is not None:
            # no-op when params carry no mesh — covers pre-sharded
            # params passed without a shard_fn too
            _check_same_mesh(self.params, sp_mesh)
        fwd_prefill = prefill_fn_for(spec_, sp_mesh, self.prefill_buckets)

        @jax.jit
        def _prefill(params, tokens, seq_lens, sampling, key):
            hidden, ks, vs = fwd_prefill(spec_, params, tokens, seq_lens)
            b = tokens.shape[0]
            last = hidden[jnp.arange(b), seq_lens - 1]
            logits = unembed(spec_, params, last)
            # first token + its logprob sampled in-program (eager sampling
            # costs a chain of device dispatches, each adding its launch
            # latency), packed into one [2, B] buffer
            first, lp = sample_tokens_with_logprobs(logits, sampling, key)
            first = jnp.stack(
                [first, jax.lax.bitcast_convert_type(lp, jnp.int32)])
            # [L, B, T, Hkv, Dh] -> [B, L, T, Hkv, Dh] so per-request slices
            # on the host are contiguous reads
            ks = jnp.swapaxes(ks, 0, 1).astype(self.kv_dtype)
            vs = jnp.swapaxes(vs, 0, 1).astype(self.kv_dtype)
            return first, ks, vs

        self._prefill = _prefill
        self.prefill_stats = LatencyStats()
        self._total_requests = 0
        self._total_prompt_tokens = 0
        self._total_handoff_bytes = 0

    def warmup(self, batch: Optional[int] = None) -> int:
        """Pre-compile one prefill program per (batch bucket × prefill
        bucket) (see ``Engine.warmup``). Returns the number of warmup
        prefills run."""
        sizes = [batch] if batch else self.batch_buckets
        runs = 0
        for n in sizes:
            for tb in self.prefill_buckets:
                prompt_len = min(tb, self.max_seq_len - 1)
                self.prefill([
                    GenerationRequest(prompt=[1] * prompt_len,
                                      max_new_tokens=1,
                                      request_id=f"warmup-{n}-{tb}-{i}")
                    for i in range(n)
                ])
                runs += 1
        return runs

    @hot_path
    def prefill(self, requests: List[GenerationRequest]) -> List[PrefillHandoff]:
        """Run one bucketed prefill batch; one handoff per request."""
        if not requests:
            return []
        if min(len(r.prompt) for r in requests) < 1:
            raise ValueError("empty prompt")
        self._total_requests += len(requests)
        n = len(requests)
        bb = _next_bucket(n, self.batch_buckets)
        # same sliding-window policy as ContinuousEngine admission: overlong
        # prompts keep their tail, capped so the decode pool has ≥1 position
        max_keep = self.max_seq_len - 1
        tb = _next_bucket(
            min(max(len(r.prompt) for r in requests), max_keep),
            self.prefill_buckets,
        )

        tokens = np.zeros((bb, tb), dtype=np.int32)
        seq_lens = np.ones((bb,), dtype=np.int32)
        temps = np.zeros((bb,), dtype=np.float32)
        top_k = np.zeros((bb,), dtype=np.int32)
        top_p = np.ones((bb,), dtype=np.float32)
        min_p = np.zeros((bb,), dtype=np.float32)
        for i, r in enumerate(requests):
            p = r.prompt[-min(tb, max_keep):]      # overlong: keep the tail
            tokens[i, : len(p)] = p
            seq_lens[i] = len(p)
            temps[i] = r.temperature
            top_k[i] = r.top_k
            top_p[i] = r.top_p
            min_p[i] = r.min_p
        sampling = SamplingParams(
            jnp.asarray(temps), jnp.asarray(top_k), jnp.asarray(top_p),
            jnp.asarray(min_p),
        )

        t0 = time.perf_counter()
        self._rng, k0 = jax.random.split(self._rng)
        first_dev, ks, vs = self._prefill(
            self.params, jnp.asarray(tokens), jnp.asarray(seq_lens),
            sampling, k0,
        )
        # graftlint: ok[host-sync-hot-path] ONE first-token read per prefill batch
        fp = np.asarray(first_dev)                 # [2, bb]: tokens; lp bits
        first = fp[0]
        first_lps = fp[1].view(np.float32)
        # graftlint: ok[host-sync-hot-path] handoff export IS a device→host bulk copy by design: the KV ships to the decode worker
        ks_np = np.asarray(jax.device_get(ks))     # [bb, L, tb, Hkv, Dh]
        # graftlint: ok[host-sync-hot-path] second half of the same handoff export
        vs_np = np.asarray(jax.device_get(vs))
        self.prefill_stats.add(time.perf_counter() - t0)

        out: List[PrefillHandoff] = []
        for i, r in enumerate(requests):
            t = int(seq_lens[i])
            # copy(): frombuffer on the receive side needs C-contiguous data,
            # and the slice must not pin the full padded batch buffer alive
            h = PrefillHandoff(
                request_id=r.request_id or f"prefill-{self._total_requests}-{i}",
                prompt_len=t,
                first_token=int(first[i]),
                first_logprob=float(first_lps[i]),
                k=ks_np[i, :, :t].copy(),                     # [L, T, Hkv, Dh]
                v=vs_np[i, :, :t].copy(),
            )
            self._total_prompt_tokens += t
            self._total_handoff_bytes += h.nbytes()
            out.append(h)
        return out

    def get_metrics(self) -> Dict[str, Any]:
        return {
            "role": "prefill",
            "total_requests": self._total_requests,
            "total_prompt_tokens": self._total_prompt_tokens,
            "total_handoff_bytes": self._total_handoff_bytes,
            "prefill": self.prefill_stats.snapshot(),
        }
