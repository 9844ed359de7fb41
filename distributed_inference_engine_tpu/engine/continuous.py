"""Continuous-batching engine over the paged HBM KV cache.

BASELINE.json configs[3] ("continuous batching + HBM paged-KV"): where
``engine.Engine`` runs one static batch to completion, this engine keeps a
fixed pool of decode slots always busy — new requests are admitted into free
slots between decode chunks while other slots are mid-generation, finished
slots return their pages immediately. The reference's batcher flushes
fixed batches (``src/batcher.py:180-200``) and its kvstore evicts whole
entries; continuous batching + page recycling is the TPU-serving
generalization of both.

Static-shape discipline (SURVEY.md §7 hard-part #1):

- Decode always runs over ALL ``max_slots`` slots — inactive slots are
  masked, not removed, so one compiled chunk program serves every occupancy.
- Prefill is bucketed per admission round (batch padded to a power-of-two
  bucket, seq to a prefill bucket): at most ``(log2(max_slots)+1) ×
  len(prefill_buckets)`` prefill programs exist.
- The decode chunk is ``lax.scan`` over ``decode_steps_per_call`` steps with
  pages donated in — zero per-token host round-trips, one small host read
  per chunk, made AFTER the next chunk is dispatched (``step``): the
  host's bookkeeping runs under a program, never between two.

Capacity discipline (SURVEY.md §7 hard-part #2): before each chunk every
active slot reserves capacity for the chunk's worst case; slots whose grant
runs out (pool pressure or ``max_seq_len``) are finished with reason
``"length"`` rather than silently indexing past their page table.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import EngineConfig, validate_prefill_compose
from ..models.base import (
    ModelSpec,
    Params,
    decode_sums,
    init_params,
    layered_family,
    prefill_sums,
    write_prefill_pages,
)
from ..ops import kda
from ..ops.sampling import SamplingParams
from ..obs.timeline import HostSpan, StepTimeline, host_span
from ..utils import compile_cache
from ..utils.hotpath import hot_path
from ..utils.tracing import LatencyStats
from .engine import _next_bucket, _pow2_buckets
from .paged_kv import PagedKVCache, page_chain_hashes
from .programs import build_programs
from .types import (
    EngineOverloadedError,
    GenerationRequest,
    GenerationResult,
    find_stop_cut,
    trim_at_stops,
)

logger = logging.getLogger(__name__)

# device-side stop-id capacity per slot (ISSUE 5b): requests with more
# single-token stop ids than this keep the extras on the host scan path
_DEVICE_STOP_K = 8


# counters every spec reports, zeros where it has none: the worker's
# counters and ``obs/collectors.py`` read these groups of any engine
_COUNTERS_OF_EVERY_SPEC = (
    "mla.decode_context_rows", "mla.decode_table_rows",
    "moe.assignments_held", "moe.assignments_total", "moe.experts_touched",
    "moe.decode_assignments_held")

# attention_impl strings a deploy may pass; anything else is refused at load
ATTENTION_IMPLS = ("auto", "xla", "pallas-decode", "pallas-decode_interpret")


def resolve_decode_body(impl: str, backend: str, spec,
                        sharded: bool = False) -> Tuple[str, str]:
    """The ONE decode body a continuous engine runs and the attention it
    implies, ``(body, attn_impl)``: a pure function of what the code can
    observe.

    ========  ==========================================  =================
    body      when                                        attention
    ========  ==========================================  =================
    hybrid    ``spec.index_topk`` (a learned selection    ``ops/sparse_
              over K|V rows), the kernel applies          index.py`` in place:
                                                          index scores over
                                                          the LIVE index-key
                                                          pages, the top-k a
                                                          counted threshold
                                                          in VMEM, the live
                                                          K|V pages read
                                                          under its mask;
                                                          else XLA: the keys
                                                          gathered through
                                                          the table,
                                                          ``lax.top_k``, the
                                                          picked rows
                                                          gathered
    hybrid    ``spec.layer_kinds`` (latent rows, or      ``ops/flash_
              K|V rows where ``spec.kv_row_lanes``        decode.py`` in
              > 0), the kernel applies                    place from the
                                                          family's ONE pool
                                                          (its latent or
                                                          its K|V kernel);
                                                          else XLA, a layer's
                                                          pages a step
    inline    uniform spec with ``sliding_window``        XLA, per-step
                                                          page scatter
    window    uniform, no window, the kernel applies      ``ops/flash_
                                                          decode.py`` in
                                                          place from the
                                                          page pool
    dense     every other uniform spec                    XLA, the context
                                                          gathered once a
                                                          chunk
    ========  ==========================================  =================

    ``"auto"`` takes the kernel on a TPU with the pool on one device and
    rows of whole 128-lane tiles (a uniform spec's fused ``Hkv·Dh``, a
    per-layer spec's K|V rows, or its latent rows, which the pool holds at
    whole tiles: ``ModelSpec.cache_row_width``); ``"pallas-decode"`` /
    ``"pallas-decode_interpret"`` ask for it by name (the tests' way to
    run the TPU body on a CPU); ``"xla"`` refuses it. A sliding-window spec
    runs ``inline`` whatever the string says: its prefix mask depends on the
    growing total length. Measured on one v5e chip at mistral-7b int4, 8
    slots (PERF.md §6, PR 25): ``window`` against ``dense`` takes the K/V
    moves from 24 % of the device's time to 6 % and the decode step from
    10.0 to 7.3 ms."""
    if impl not in ATTENTION_IMPLS:
        raise ValueError(
            f"attention_impl {impl!r} is not one of {ATTENTION_IMPLS}")
    # lanes of a row the kernel would copy: a uniform spec's K (or V), those
    # of a per-layer spec whose paged layers keep K|V rows, or its latent row
    lanes = ((spec.kv_row_lanes or spec.cache_row_width) if spec.layer_kinds
             else spec.n_kv_heads * spec.head_dim)
    if spec.sliding_window and not spec.layer_kinds:
        return "inline", "xla"
    if impl == "auto":
        kernel = backend == "tpu" and not sharded and lanes % 128 == 0
        impl = "pallas-decode" if kernel else "xla"
    if spec.layer_kinds:
        if impl != "xla" and lanes % 128:
            raise ValueError(
                f"attention_impl {impl!r}: the kernel copies rows of "
                f"whole 128-lane tiles, this spec's have {lanes}")
        return "hybrid", impl
    return ("dense" if impl == "xla" else "window"), impl


class _Slot:
    """Host-side bookkeeping for one live sequence."""

    __slots__ = ("request", "slot_id", "prompt_len", "produced", "tokens",
                 "logprobs", "submitted_at", "admitted_at", "first_token_at",
                 "on_tokens", "streamed", "stop_cut", "first_pending")

    def __init__(self, request: GenerationRequest, slot_id: int,
                 prompt_len: int, t_submit: float, t_admit: float,
                 on_tokens=None) -> None:
        self.request = request
        self.slot_id = slot_id
        self.prompt_len = prompt_len
        self.produced = 0
        self.tokens: List[int] = []
        self.logprobs: List[float] = []
        # perf_counter stamps, handed up on the result (``stamps``): the
        # TTFT clock starts at SUBMIT (queue wait while slots/pages were
        # busy is exactly the latency a loaded engine must report);
        # admitted = slot held and prefill dispatched
        self.submitted_at = t_submit
        self.admitted_at = t_admit
        self.first_token_at = 0.0
        self.on_tokens = on_tokens      # streaming: cb(new_tokens: List[int])
        self.streamed = 0               # tokens already emitted to the cb
        self.stop_cut = -1              # earliest stop cut, once found
        self.first_pending = False      # the prefill-sampled first token is
                                        # still in the prefill's own output
                                        # on the device (_read_firsts)


class _PrefillProgress:
    """A long prompt mid-way through chunked prefill: its slot and pages are
    allocated, but it is not yet decoding (not in ``_slots``)."""

    __slots__ = ("request", "prompt", "done", "on_tokens", "t_submit",
                 "t_admit")

    def __init__(self, request: GenerationRequest, prompt: List[int],
                 on_tokens, t_submit: float, t_admit: float) -> None:
        self.request = request
        self.prompt = prompt
        self.done = 0                   # tokens already prefilled (page-aligned)
        self.on_tokens = on_tokens
        self.t_submit = t_submit
        self.t_admit = t_admit          # slot held, first chunk dispatched


class _ChunkEntry:
    """The decode chunk in flight: its packed output on its way to the
    host, and what reading it later needs. The engine keeps ONE (the
    chunk it dispatched last, ``_pending``) and reads it after it has
    dispatched the next. ``snapshot`` are the ``_Slot`` objects live at
    the dispatch: a column belongs to that object, never to a later
    tenant of its slot. ``caps`` is the per-slot token capacity the chunk
    ran with, which tells a row the device PAUSED at its grant from one
    that finished. ``handed_on``: slots whose row is certain to end in
    this chunk and whose slot went to a successor while it ran
    (``_hand_on_foreseen``); their last tokens and results come out of
    this entry's read. ``idle``: slots revived after this chunk was
    dispatched, so their row sits it out."""

    __slots__ = ("packed", "n_steps", "snapshot", "caps", "handed_on",
                 "idle")

    def __init__(self, packed, n_steps: int, snapshot: Dict[int, _Slot],
                 caps: List[int]) -> None:
        self.packed = packed
        self.n_steps = n_steps
        self.snapshot = snapshot
        self.caps = caps
        self.handed_on: set = set()
        self.idle: set = set()


class _SwapRecord:
    """A decode sequence preempted to the host tier: its ``_Slot`` state
    plus the exact device KV it held. Invariant carried across the swap:
    the KV covers exactly ``kv_len`` positions and ``state.tokens[-1]`` is
    the latest sampled token, NOT yet written to KV — precisely the shape
    ``_install`` expects, so resume is an install, never a prefill."""

    __slots__ = ("state", "kv_len", "k_pages", "v_pages", "nbytes")

    def __init__(self, state: "_Slot", kv_len: int,
                 k_pages: List[np.ndarray], v_pages: List[np.ndarray],
                 nbytes: int) -> None:
        self.state = state
        self.kv_len = kv_len
        self.k_pages = k_pages
        self.v_pages = v_pages
        self.nbytes = nbytes


class ContinuousEngine:
    """Slot-based continuous batching over a paged KV cache.

    Synchronous pump: callers enqueue with ``submit`` and drive ``step()``
    (or ``run_until_idle``); the async serving layer wraps this in its
    executor thread exactly like ``Engine.generate``.

    With ``EngineConfig.prefill_chunk`` set, prompts longer than the chunk
    prefill incrementally — one chunk per engine step, interleaved with
    decode chunks — so admitting a long prompt stalls live decodes for one
    bounded chunk instead of the whole prompt (the inter-token-latency
    cliff SURVEY.md §7 hard-part #3 describes; chunked prefill is the
    single-pool alternative to disaggregation, which ``engine/disagg.py``
    provides for two pools).
    """

    def __init__(
        self,
        spec: ModelSpec,
        params: Optional[Params] = None,
        config: Optional[EngineConfig] = None,
        seed: int = 0,
        shard_fn=None,
        kv_sharding=None,   # NamedSharding for the page pools (tp serving;
                            # parallel.sharding.ModelShardings.paged_kv)
        sp_mesh=None,       # optional mesh with a real sp axis: ADMISSION
                            # prefill runs sequence-parallel ring attention
                            # (long prompts stall decode 1/sp as long, the
                            # same concern prefill_chunk addresses in time
                            # rather than space — the two are exclusive)
        artifact_path=None,       # pre-fused serving artifact
                            # (engine/artifact.py): restore the prepared
                            # tree instead of init/quantize/fuse/pad; spec
                            # may be None (the sidecar is authoritative)
        artifact_selfcheck=True,  # replay the golden-token probe before
                            # admitting traffic (mismatch raises
                            # ArtifactCorruptError, never serves wrong
                            # numerics)
    ) -> None:
        self.config = config or EngineConfig()
        cfg = self.config
        self.artifact_manifest: Optional[Dict[str, Any]] = None
        if (artifact_path is not None and spec is not None
                and spec.layer_kinds):
            raise ValueError("a per-layer (hybrid) spec does not support: "
                             "a serving artifact")
        if artifact_path is not None:
            from .artifact import load_artifact

            a_spec, params, self.artifact_manifest = load_artifact(
                artifact_path)
            if spec is None:
                spec = a_spec
        self.spec = spec.validate()
        # the ONE decode body this engine runs and the attention it implies
        # ("auto" never survives): get_metrics()["attn_impl"] and the
        # worker's device report. Before any weight exists: a mistyped
        # string must not pay an 8B-scale init first.
        self.body, self.attn_impl = resolve_decode_body(
            cfg.attention_impl, jax.default_backend(), self.spec,
            sharded=shard_fn is not None or kv_sharding is not None)
        # two facts of a per-layer spec, kept apart. ``_per_layer``: its
        # family has ONE prefill, whole prompts from nothing, and none that
        # continues from cached pages, so every path that would resume on
        # pages (a prefix hit, a prefill chunk, the host tier, an imported
        # prefix) is refused, and a sequence that cannot grow re-prefills.
        # ``_recurrent``: some layers also keep a per-slot state that pages
        # do not carry; it only words the messages.
        # Each refusal fails here, at load, not at its first request.
        self._per_layer = bool(self.spec.layer_kinds)
        self._recurrent = self.spec.recurrent
        # the module that runs a per-layer spec (models.base.layered_family)
        self._family = (layered_family(self.spec) if self._per_layer
                        else None)
        if self._per_layer:
            refused = [name for name, on in (
                ("a tp/sp mesh (shard_fn / kv_sharding / sp_mesh)",
                 shard_fn is not None or kv_sharding is not None
                 or sp_mesh is not None),
                ("kv_offload (swap pre-emption and the KV fabric ride it)",
                 bool(getattr(cfg, "kv_offload", False))),
                ("prefill_chunk (chunked prefill continues from pages)",
                 bool(cfg.prefill_chunk)),
            ) if on]
            if refused:
                raise ValueError(
                    "a per-layer (hybrid) spec does not support: "
                    + "; ".join(refused))
        if params is None:
            init = self._family.init_params if self._family else init_params
            params = init(spec, jax.random.key(seed))
        if shard_fn is not None:
            params = shard_fn(params)
        if self.spec.layer_kinds:
            from ..ops.quant import QuantizedTensor

            if any(isinstance(leaf, QuantizedTensor)
                   for leaf in jax.tree_util.tree_leaves(
                       params,
                       is_leaf=lambda x: isinstance(x, QuantizedTensor))):
                raise ValueError("a per-layer (hybrid) spec serves an "
                                 "unquantized tree only")
            self.params = params     # no stacked blocks to fuse
        elif self.artifact_manifest is not None:
            # the artifact IS the post-prepare tree — re-preparing would
            # re-pay the fuse/pad cost the fast path exists to skip
            self.params = params
        else:
            from ..ops.quant import prepare_params

            # kernel-mode selection (sharded int4 -> "cp") + qkv/gate+up
            # payload fusion, shared across engines (ops.quant.prepare_params)
            self.params = prepare_params(params)
        self._rng = jax.random.key(seed + 1)

        self.max_slots = cfg.max_slots
        max_seq = min(cfg.max_seq_len, spec.max_seq_len)
        # host-RAM second tier (engine/kv_offload.py): evictions offload,
        # admissions prefetch, pool exhaustion swaps instead of finishing
        self._offload = None
        if getattr(cfg, "kv_offload", False):
            from .kv_offload import HostKVOffload

            self._offload = HostKVOffload(
                max_bytes=int(getattr(cfg, "kv_offload_bytes", 1 << 30)))
        self.kv = PagedKVCache(
            spec, max_slots=cfg.max_slots, page_size=cfg.page_size,
            num_pages=cfg.num_pages, max_seq_len=max_seq,
            dtype=cfg.kv_dtype, sharding=kv_sharding,
            offload=self._offload,
        )
        self.prefill_buckets = sorted(
            {b for b in cfg.prefill_buckets if b < max_seq} | {max_seq}
        )
        self.max_seq_len = max_seq
        self.prefix_cache = bool(cfg.prefix_cache)
        # a page hit needs a prefill that continues from the cached pages,
        # which a per-layer family does not have (and, with recurrent
        # layers, the state AT THAT POSITION, which nothing keeps): prefix
        # reuse is off for such a spec, from the spec, whatever the option
        # says (counted, so a deploy that asked sees it)
        self._prefix_disabled_per_layer = int(
            self._per_layer and self.prefix_cache)
        if self._per_layer:
            self.prefix_cache = False
        # the family's counters by name, ``<group>.<key>`` of
        # ``get_metrics()``: what a decode chunk's packed output carries
        # (the family's DECODE_COUNTERS, no read of their own), what its
        # prefills return (PREFILL_COUNTERS) and the host sums of each
        # (``models.base.decode_sums`` / ``prefill_sums``, whose names an
        # empty chunk and prompt give)
        fam = self._family
        no_rows = np.zeros((0,), np.int64)
        self._counters: Dict[str, int] = dict.fromkeys(
            _COUNTERS_OF_EVERY_SPEC + (() if fam is None else tuple(
                n for n in (*fam.DECODE_COUNTERS, *fam.PREFILL_COUNTERS,
                            *decode_sums(self.spec, no_rows, no_rows),
                            *prefill_sums(self.spec, 0, 1)) if n)), 0)
        self._decode_steps = 0
        # prefill programs' counters stay on the device until a chunk's
        # harvest has synced past them (read then, without a wait)
        self._prefill_counters: List[Any] = []
        # sequences pre-empted by re-prefill: request id -> what they had
        # produced before (merged into the result at the finish)
        self._resumed: Dict[str, Dict[str, Any]] = {}
        self._reprefill_preemptions = 0
        # the body that moves a recurrent state in a decode step, as the
        # family's programs will pick it when they are traced (ops/kda.py)
        self.state_step_body = kda.step_impl() if self._recurrent else None
        # the decode chunk in flight (see _ChunkEntry): read after the NEXT
        # one is dispatched, so the host's bookkeeping runs under a program
        self._pending: Optional[_ChunkEntry] = None
        # prefills whose sampled first tokens are still on the device:
        # (the prefill's [2, bb] output, when it was dispatched, [(column,
        # _Slot)]), read after the next decode dispatch (_read_firsts)
        self._first_reads: List[
            Tuple[Any, float, List[Tuple[int, _Slot]]]] = []
        self._emit_carried_chunks = 0    # streamed under a later dispatch
        self._emit_flushed_chunks = 0    # streamed with nothing to hide it
        # the open engine.decode.dispatch bracket and its closing args:
        # closed at the end of the step's blocking read (_close_dispatch)
        self._open_dispatch: Optional[Tuple[HostSpan, Dict[str, Any]]] = None
        self._ctx_page_buckets = _pow2_buckets(self.kv.max_pages_per_seq)
        self._prefix_hit_admissions = 0
        # chunked prefill: chunk must be page-aligned so every suffix chunk
        # starts on a page boundary (the context gather reads whole pages)
        ps = self.kv.page_size
        self._chunk = (max(ps, cfg.prefill_chunk // ps * ps)
                       if cfg.prefill_chunk else 0)
        self._prefilling: Dict[int, _PrefillProgress] = {}   # slot -> progress
        self._chunked_admissions = 0
        # does a freed slot find its successor here? Admissions, those of
        # them whose request was already queued when the slot it took was
        # freed, and decode dispatches sent with a free slot and nothing
        # queued (a slot that waits for a request still on its way)
        self._admissions = 0
        self._admissions_from_queue = 0
        self._empty_slot_dispatches = 0
        self._slot_freed_at = [0.0] * self.max_slots    # perf_counter
        # how often the one sequence engages: successors prefilled behind
        # the chunk their predecessor ends in; finishes only a read could
        # tell (EOS, a stop, a row paused at its grant), each one chunk of
        # one slot; seconds blocked in the packed reads (near zero a chunk:
        # the overlap holds); iterations that read the chunk in flight
        # BEFORE dispatching, the pool unable to back a chunk ahead of it
        self._admissions_ahead = 0
        self._finishes_learned_late = 0
        self._harvest_wait_s = 0.0
        self._sync_fallback_iterations = 0

        # ---- queues / state: (request, stream cb or None, t_submit)
        self._waiting: Deque[Tuple[GenerationRequest, Any, float]] = (
            collections.deque()
        )
        # disaggregated admissions whose prefill already ran on a
        # prefill-pool worker (engine/disagg.py):
        # (request, handoff, cb, t_submit)
        self._waiting_prefilled: Deque[
            Tuple[GenerationRequest, Any, Any, float]] = (
            collections.deque()
        )
        self._slots: Dict[int, _Slot] = {}
        self._finished: List[GenerationResult] = []
        # swap-based preemption: victims parked on the host tier, resumed
        # FIFO when pages free up (_SwapRecord list; offload tier only)
        self._swapped: Deque["_SwapRecord"] = collections.deque()

        # device-side per-slot state [max_slots]
        n = cfg.max_slots
        self._lengths = jnp.zeros((n,), jnp.int32)
        self._last = jnp.zeros((n,), jnp.int32)
        self._active = jnp.zeros((n,), bool)
        self._produced = jnp.zeros((n,), jnp.int32)
        self._max_new = jnp.zeros((n,), jnp.int32)
        self._eos = jnp.full((n,), -1, jnp.int32)
        self._temps = jnp.zeros((n,), jnp.float32)
        self._top_k = jnp.zeros((n,), jnp.int32)
        self._top_p = jnp.ones((n,), jnp.float32)
        self._min_p = jnp.zeros((n,), jnp.float32)
        # device-side stop ids (ISSUE 5b): the first _DEVICE_STOP_K
        # single-token stops per slot ride a [n, K] matrix so the decode
        # loop retires a stopped slot IN-CHUNK instead of generating (and
        # paying bandwidth for) up to n_steps-1 dead tokens until the
        # host scan catches up. Host find_stop_cut stays the source of
        # truth: overflow ids and multi-token stop_sequences still retire
        # there, and _finish's trim_at_stops names the reason either way.
        self._stops_dev = jnp.full((n, _DEVICE_STOP_K), -1, jnp.int32)
        # live slots whose row holds real ids: when empty (the common
        # case) dispatches select the stop-free program variant, so
        # engines that never see stop_ids never pay the extra compile
        self._stop_slots: set = set()
        # host mirror of per-slot lengths: the capacity loop consults it
        # every step, and a device readback costs a blocking host round
        # trip. Updated on admission and
        # from each chunk's packed output row. (Active flags need no
        # mirror — each chunk's packed row is consumed immediately.)
        self._lengths_host = np.zeros((n,), np.int32)

        # ---- jitted programs (engine/programs.py)
        has_sp = (sp_mesh is not None
                  and sp_mesh.shape.get("sp", 1) > 1)
        # compose rule lifted into config.validate_prefill_compose so
        # metadata-driven loaders reject the pair before weights load;
        # kept here too for engines constructed directly
        validate_prefill_compose(self._chunk, sp=2 if has_sp else 1)
        if has_sp:
            from .engine import _check_same_mesh

            # fail the deploy, not the first admission trace (no-op when
            # params carry no mesh — covers pre-sharded params too)
            _check_same_mesh(self.params, sp_mesh)
            if self.prefix_cache:
                # a cache hit prefills its UNIQUE suffix through the dense
                # suffix program — an arbitrarily long tail would stall
                # decode unbounded, the very thing sp exists to bound, so
                # an sp deploy prefers whole-prompt ring prefill over
                # prefix reuse until a sequence-parallel suffix program
                # exists
                logger.info("sp prefill disables the prefix cache "
                            "(dense suffix program; see ContinuousEngine)")
                self.prefix_cache = False
        from ..parallel.long_context import prefill_fn_for

        # fused prefill+page-write for batched admissions; the sp path
        # keeps the two-program shape (ring prefill returns stacked KV)
        (self._prefill, prefill_pages, self._prefill_suffix,
         self._decode_chunk, self._install,
         self._install_first) = build_programs(
            self.spec, self.body, self.attn_impl, self._family,
            prefill_fn_for(self.spec, sp_mesh, self.prefill_buckets),
            self.kv.page_size, self.max_seq_len)
        self._prefill_pages = None if has_sp else prefill_pages
        # decode chunks dispatched; get_metrics() reports them by how
        # attention reached the context: a Pallas kernel reading the page
        # pool where it lies, or the dense per-chunk copy
        self._decode_chunks = 0
        # page-pool writes donate the pool: an un-donated eager scatter
        # would materialise a full copy of the (possibly multi-GiB) pages
        # on every admission
        self._write_pages = jax.jit(write_prefill_pages,
                                    donate_argnums=(0, 1))

        # ---- metrics
        self.prefill_stats = LatencyStats()
        self.chunk_stats = LatencyStats()
        self._total_requests = 0
        self._total_generated = 0
        self._total_prompt_tokens = 0
        self._admission_denied = 0
        self._rejected_full = 0        # submits refused: queue at cap
        self._shed_deadline = 0        # queued requests shed past deadline
        self._deadline_expired = 0     # per-request deadline_s expiries
        self._capacity_finishes = 0
        self._swap_outs = 0         # decode victims parked on the host tier
        self._swap_resumes = 0      # parked victims back in a slot (no prefill)
        self._swap_fallbacks = 0    # host budget refused a swap -> "length"
        self._steps = 0
        self._prefill_calls = 0     # batched-admission dispatches
        self._occupancy_sum = 0     # Σ live slots per step (occupancy)
        self.ttft_stats = LatencyStats()   # per-request, from submit
        self.queue_wait_stats = LatencyStats()   # submit -> admitted
        # step timeline (obs/timeline.py): one record per host span of the
        # engine thread (device dispatches among them), exported as a
        # Perfetto-loadable Chrome trace. A dispatch record that compiled
        # says what and for how long (``obs.timeline.compile_keys``).
        cap = int(getattr(config, "timeline_capacity", 4096) or 0)
        self.timeline: Optional[StepTimeline] = (
            StepTimeline(capacity=cap, name="continuous") if cap else None)
        # set-up seen from inside (``warmup`` / ``get_metrics``): the warm-
        # up grid's rounds, each split by the compile log's records, and
        # the log index + counters at which warm-up last ended (engine
        # construction until then): what compiles after is named
        self._warmup_rounds: List[Dict[str, Any]] = []
        self._warm_log_index = compile_cache.log_index()
        self._warm_counters = compile_cache.compile_counters()   # installs
        # host-gap split (ISSUE 5 satellite): dispatch-bracket seconds vs
        # the host-side gap BETWEEN consecutive dispatch brackets. Counted
        # even with the timeline ring disabled. A decode bracket runs from
        # the dispatch of chunk k+1 to the end of the blocking read of
        # chunk k that follows it; what the host does from there to the
        # next dispatch (appends, judgments, streaming, admission, the
        # capacity loop) is gap on the host's clock, and runs under chunk
        # k+1 on the device's: ``harvest_wait_s_total`` says whether the
        # device was still busy when the host came back to read.
        self._dispatch_s = 0.0
        self._host_gap_s = 0.0
        self._last_dispatch_end: Optional[float] = None
        # overlap hook (ISSUE 5c): called on the ENGINE thread right
        # after each chunk dispatch, while the device is busy. The
        # serving pump wires its inbox drain (batch formation) here so
        # admission work rides the device step's shadow instead of the
        # gap between steps. The hook must only enqueue (engine.submit);
        # it must NOT call step()/install paths.
        self.overlap_hook: Optional[Any] = None
        self._stream_clamped_chunks = 0   # chunks shortened for streaming

        if self.artifact_manifest is not None and artifact_selfcheck:
            # golden-token self-check BEFORE any traffic: replays the
            # save-time probe against the restored tree through the real
            # admission/decode programs (also a bb=1 warmup). Raises
            # ArtifactCorruptError on divergence — callers fall back to
            # the slow path rather than serve wrong numerics.
            from .artifact import verify_golden

            verify_golden(self, self.artifact_manifest)

    # ------------------------------------------------------------- submit

    def submit(self, request: GenerationRequest, on_tokens=None) -> str:
        """Enqueue; returns the request id (assigned if empty).

        ``on_tokens`` (optional) streams incremental output: called on the
        engine's thread with each batch of newly generated tokens, already
        trimmed to ``max_new_tokens``/EOS — the final ``GenerationResult``
        remains authoritative and contains the full sequence."""
        if not request.prompt:
            raise ValueError("empty prompt")
        self._check_admission_cap()
        self._total_requests += 1
        if not request.request_id:
            request.request_id = f"creq-{self._total_requests}"
        self._waiting.append((request, on_tokens, time.perf_counter()))
        return request.request_id

    def submit_prefilled(self, request: GenerationRequest, handoff: Any,
                         on_tokens=None) -> str:
        """Enqueue a request whose prefill ran on a prefill-pool worker.

        ``handoff`` is an ``engine.disagg.PrefillHandoff``: the prompt KV
        (``[L, T, Hkv, Dh]`` numpy, already in the cache dtype) plus the
        first sampled token. Admission scatters the KV into paged slots and
        decoding proceeds exactly as for a locally-prefilled sequence.

        TTFT caveat: the clock starts HERE — the prefill-pool hop happened
        in another process whose monotonic clock is not comparable, so
        disaggregated ``ttft_s`` covers this decode worker only; the
        coordinator's ``RequestTrace`` carries the end-to-end latency.
        """
        if self.spec.layer_kinds:
            raise ValueError(
                "disaggregated prefill hands over K/V pages; a per-layer "
                "(hybrid) spec keeps latent rows and a recurrent state")
        L, T, Hkv, Dh = handoff.k.shape
        if (L, Hkv, Dh) != (self.spec.n_layers, self.spec.n_kv_heads,
                            self.spec.head_dim):
            raise ValueError(
                f"handoff KV shape {handoff.k.shape} does not match model "
                f"(L={self.spec.n_layers}, Hkv={self.spec.n_kv_heads}, "
                f"Dh={self.spec.head_dim})"
            )
        pl = handoff.prompt_len
        if (T != pl - handoff.kv_start or pl < 1 or pl >= self.max_seq_len
                or not 0 <= handoff.kv_start < pl):
            raise ValueError(
                f"handoff prompt_len {pl} / kv_start {handoff.kv_start} / "
                f"KV T {T} inconsistent or beyond max_seq_len "
                f"{self.max_seq_len}"
            )
        if handoff.kv_start and not self.prefix_cache:
            raise ValueError(
                "delta handoff (kv_start > 0) needs the decode engine's "
                "prefix cache enabled")
        self._check_admission_cap()
        self._total_requests += 1
        if not request.request_id:
            request.request_id = f"creq-{self._total_requests}"
        self._waiting_prefilled.append((request, handoff, on_tokens,
                                        time.perf_counter()))
        return request.request_id

    # ----------------------------------------------------------- overload

    def _check_admission_cap(self) -> None:
        """Hard backpressure at submit: a bounded waiting queue is the
        difference between overload degrading service and overload growing
        an unbounded deque until the host dies (VERDICT r2 item 2)."""
        cap = self.config.max_waiting
        if cap and self.n_waiting >= cap:
            self._rejected_full += 1
            raise EngineOverloadedError(
                f"waiting queue full ({self.n_waiting}/{cap}); "
                "retry on another replica or later", reason="queue_full")

    def _shed_expired(self) -> None:
        """Deadline-based shedding, two budgets checked at step start —
        before any prefill/decode work is spent on the victim:

        - the engine-wide ``queue_deadline_s`` (overload control): a
          request still queued past it resolves with
          ``finish_reason="overloaded"`` (reason "deadline", zero tokens,
          ttft = its queue wait) — the pump converts the outcome into the
          typed ``EngineOverloadedError`` for RPC clients;
        - the request's OWN ``deadline_s`` budget (the client deadline the
          coordinator propagates in RPC metadata): expiry resolves with
          ``finish_reason="deadline"`` and is never retried upstream —
          the client already stopped caring.
        """
        queue_deadline = self.config.queue_deadline_s
        now = time.perf_counter()
        cut = (now - queue_deadline) if queue_deadline else None
        for q, t_idx in ((self._waiting, 2), (self._waiting_prefilled, 3)):
            if not q:
                continue
            # FIFO queues: the head is the oldest, so the global budget is
            # an O(1) head check; per-request deadlines need the scan, but
            # only when some queued request actually carries one.
            if not (cut is not None and q[0][t_idx] <= cut) and not any(
                    item[0].deadline_s is not None for item in q):
                continue
            keep = type(q)()
            for item in q:
                req, t = item[0], item[t_idx]
                if cut is not None and t <= cut:
                    self._shed_deadline += 1
                    self._finished.append(GenerationResult(
                        request_id=req.request_id,
                        tokens=[],
                        finish_reason="overloaded",
                        prompt_tokens=len(req.prompt),
                        ttft_s=now - t,
                        decode_s=0.0,
                        metadata={"overload_reason": "deadline"},
                        stamps={"submitted": t},
                    ))
                elif req.deadline_s is not None and now - t >= req.deadline_s:
                    self._deadline_expired += 1
                    self._finished.append(GenerationResult(
                        request_id=req.request_id,
                        tokens=[],
                        finish_reason="deadline",
                        prompt_tokens=len(req.prompt),
                        ttft_s=now - t,
                        decode_s=0.0,
                        metadata={"deadline_s": req.deadline_s},
                        stamps={"submitted": t},
                    ))
                else:
                    keep.append(item)
            if len(keep) != len(q):
                q.clear()
                q.extend(keep)

    # ---------------------------------------------------------- admission

    def _admit_prefilled(self) -> int:
        """Admit handed-off sequences: write their KV into pages, no local
        prefill program — the disaggregated half of ``_try_admit``.

        Prefix-aware: with the prefix cache on, admission allocates via
        ``alloc_slot_prefix`` so cached prompt-head pages are REUSED (and
        a delta handoff — ``kv_start > 0`` — only ships/writes the tail).
        The probe that trimmed the handoff was advisory; if the cached
        prefix shrank in flight (pages reclaimed), the request resolves
        with the typed ``stale_prefix`` outcome and the sender re-ships
        full KV. Admitted prompts register their pages, so disaggregated
        traffic fills the decode pool's prefix cache exactly like local
        admissions do."""
        admitted = 0
        while self._waiting_prefilled:
            req, handoff, on_tok, t_submit = self._waiting_prefilled[0]
            prompt_len = handoff.prompt_len
            # the tokens the prefill pool actually ran (it tail-truncates
            # overlong prompts exactly like submit())
            tok = req.prompt[-prompt_len:]
            n_cached = 0
            if self.prefix_cache:
                got = self.kv.alloc_slot_prefix(tok)
                if got is None:
                    self._admission_denied += 1
                    break
                slot, n_cached = got
                if n_cached < handoff.kv_start:
                    # advisory probe went stale: the handoff lacks KV for
                    # [n_cached, kv_start) — typed outcome, sender retries
                    # with the full payload
                    self.kv.free_slot(slot)
                    self._waiting_prefilled.popleft()
                    self._finished.append(GenerationResult(
                        request_id=req.request_id, tokens=[],
                        finish_reason="stale_prefix",
                        prompt_tokens=prompt_len,
                        metadata={"kv_start": handoff.kv_start,
                                  "cached_now": n_cached}))
                    continue
            else:
                slot = self.kv.alloc_slot(prompt_len)
                if slot is None:
                    self._admission_denied += 1
                    break
            self._waiting_prefilled.popleft()
            admitted += 1
            admit = self._span("engine.admit", rows=1,
                               prompt_tokens=prompt_len,
                               request_ids=req.request_id)
            sp = self._dispatch_span("engine.prefill.dispatch", rows=1,
                                     prefill_tokens=prompt_len)
            # write only [n_cached, prompt_len) — the cached head pages are
            # shared; pad the tail to a prefill bucket so the scatter
            # reuses the same compiled shapes as local admission
            tail = prompt_len - n_cached
            off = n_cached - handoff.kv_start   # offset into handoff rows
            tb = _next_bucket(tail, self.prefill_buckets)
            L, _, Hkv, Dh = handoff.k.shape
            ks = np.zeros((L, 1, tb, Hkv, Dh), dtype=handoff.k.dtype)
            vs = np.zeros_like(ks)
            ks[:, 0, :tail] = handoff.k[:, off:]
            vs[:, 0, :tail] = handoff.v[:, off:]
            self.kv.sync_tiers()       # flush host-tier traffic pre-write
            kp, vp = self._write_pages(
                self.kv.k_pages, self.kv.v_pages,
                jnp.asarray(ks), jnp.asarray(vs),
                self.kv.page_table[slot: slot + 1],
                jnp.asarray([tail], jnp.int32),
                start=jnp.asarray([n_cached], jnp.int32),
            )
            self.kv.swap(kp, vp)
            if self.prefix_cache:
                self.kv.register_prefix(slot, tok)
                if n_cached:
                    self._prefix_hit_admissions += 1
            self._total_prompt_tokens += prompt_len
            self._install_slot(req, slot, prompt_len, handoff.first_token,
                               sp, on_tok, t_submit=t_submit,
                               t_admit=time.perf_counter(),
                               first_lp=getattr(handoff, "first_logprob",
                                                0.0))
            admit.close()
        return admitted

    def _count_admission(self, slot: int, t_submit: float,
                         t_admit: float) -> None:
        self.queue_wait_stats.add(t_admit - t_submit)
        self._admissions += 1
        if t_submit < self._slot_freed_at[slot]:
            self._admissions_from_queue += 1
        if self._pending is not None and slot in self._pending.handed_on:
            self._admissions_ahead += 1

    def _release_slot(self, slot: int) -> None:
        """Free a slot a sequence held, and note when."""
        self.kv.free_slot(slot)
        self._slot_freed_at[slot] = time.perf_counter()

    def _pack_rows(self, rows: List[Dict[str, Any]]):
        """Pad an admission round's rows to a pow2 bucket of device-ready
        arrays (shared by the two installs). Pad entries
        hold ``max_slots`` and fall out of the scatters' range. Also
        updates the host length mirror."""
        bb = 1 << (len(rows) - 1).bit_length()
        slots = np.full((bb,), self.max_slots, np.int32)   # pad -> dropped
        f = {k: np.zeros((bb,), dt) for k, dt in (
            ("prompt_len", np.int32), ("first", np.int32),
            ("max_new", np.int32), ("eos", np.int32),
            ("temp", np.float32), ("top_k", np.int32),
            ("top_p", np.float32), ("min_p", np.float32))}
        stops = np.full((bb, _DEVICE_STOP_K), -1, np.int32)
        for i, r in enumerate(rows):
            slots[i] = r["slot"]
            self._lengths_host[r["slot"]] = r["prompt_len"]
            stops[i, : len(r["stops"])] = r["stops"]
            (self._stop_slots.add if r["stops"]
             else self._stop_slots.discard)(r["slot"])
            for k in f:
                f[k][i] = r[k]
        vals = {k: jnp.asarray(v) for k, v in f.items()}
        vals["stops"] = jnp.asarray(stops)
        return bb, jnp.asarray(slots), vals

    def _install_device(self, rows: List[Dict[str, Any]], cols=None,
                        first_dev=None) -> None:
        """Install device state for a round of admissions in one dispatch;
        ``rows`` entries carry slot + per-slot fields. With ``first_dev``
        (a local prefill's output, on the device) the first tokens are
        wired from it, column ``cols[i]`` for ``rows[i]``
        (``vals["first"]`` goes unused): no host round trip."""
        if not rows:
            return
        bb, slots, vals = self._pack_rows(rows)
        state = (self._lengths, self._last, self._active, self._produced,
                 self._max_new, self._eos, self._temps, self._top_k,
                 self._top_p, self._min_p, self._stops_dev)
        if first_dev is None:
            state = self._install(*state, slots, vals)
        else:
            cols_np = np.zeros((bb,), np.int32)
            cols_np[: len(cols)] = cols
            state = self._install_first(*state, slots, vals, first_dev,
                                        jnp.asarray(cols_np))
        (self._lengths, self._last, self._active, self._produced,
         self._max_new, self._eos, self._temps, self._top_k,
         self._top_p, self._min_p, self._stops_dev) = state

    @staticmethod
    def _slot_row(req: GenerationRequest, slot: int, prompt_len: int,
                  first: int) -> Dict[str, Any]:
        return {"slot": slot, "prompt_len": prompt_len, "first": first,
                "max_new": req.max_new_tokens, "eos": req.eos_id,
                "temp": req.temperature, "top_k": req.top_k,
                "top_p": req.top_p, "min_p": req.min_p,
                "stops": list(req.stop_ids or ())[:_DEVICE_STOP_K]}

    def _install_slot(self, req: GenerationRequest, slot: int,
                      prompt_len: int, first: int, dispatch: HostSpan,
                      on_tokens, t_submit: float, t_admit: float,
                      first_lp: float = 0.0) -> None:
        """Tail of an admission whose first token the HOST already holds
        (a disaggregated handoff: ``_admit_prefilled``); a local prefill's
        rows go through ``_seat``. ``dispatch`` is the open page-write
        bracket (it feeds the prefill-latency histogram); ``t_submit``
        starts the request's TTFT clock (queue wait included)."""
        self.prefill_stats.add(time.perf_counter() - dispatch.t0)
        self._tl_record(dispatch)
        state = _Slot(req, slot, prompt_len, t_submit, t_admit, on_tokens)
        state.tokens.append(first)
        state.logprobs.append(first_lp)
        state.produced = 1
        state.first_token_at = time.perf_counter()
        self.ttft_stats.add(state.first_token_at - t_submit)
        self._count_admission(slot, t_submit, t_admit)
        self._slots[slot] = state
        self._emit_stream(state)
        state.stop_cut = find_stop_cut([first], req)
        if state.stop_cut >= 0 or req.max_new_tokens <= 1:
            self._finish(slot, "stop" if state.stop_cut >= 0 else "length")
        else:
            self._install_device(
                [self._slot_row(req, slot, prompt_len, first)])

    def _seat(self, rows: List[Tuple], first_dev, t_sent: float) -> None:
        """Tail of every local prefill (dispatched at ``t_sent``): its rows
        ``(request, stream cb, slot, prompt_len, t_submit, t_admit, column
        of first_dev)`` come up on the device with their first tokens
        wired from ``first_dev`` where it lies (``_install_first``), so
        admission never blocks the decode dispatch that follows; the host
        reads ``first_dev`` after that dispatch (``_read_firsts``). A request that ends with its first
        token (``max_new_tokens <= 1``) never decodes: it is not installed
        and gives its slot back at once (device order keeps its pages
        until the prefill has written them)."""
        install: List[Dict[str, Any]] = []
        cols: List[int] = []
        reads: List[Tuple[int, _Slot]] = []
        for req, cb, slot, prompt_len, t_submit, t_admit, col in rows:
            state = _Slot(req, slot, prompt_len, t_submit, t_admit, cb)
            state.first_pending = True
            self._count_admission(slot, t_submit, t_admit)
            reads.append((col, state))
            if req.max_new_tokens <= 1:
                self._release_slot(slot)
                state.slot_id = -1
                continue
            self._slots[slot] = state
            install.append(self._slot_row(req, slot, prompt_len, 0))
            cols.append(col)
        self._install_device(install, cols, first_dev)
        if reads:
            self._first_reads.append((first_dev, t_sent, reads))

    def _read_firsts(self) -> None:
        """Deliver the first tokens of the prefills dispatched since the
        last call: one blocking read of each prefill's own [2, bb] output.
        ``_step`` calls it AFTER the decode dispatch and the read of the
        chunk before it (the device runs that chunk, then the prefill, then
        the new chunk), so the wait is the prefill's and the first frame
        leaves a chunk before the packed output that follows it. A first
        token that already ends its request (EOS, a stop, a budget of one)
        finishes it here."""
        if not self._first_reads:
            return
        reads, self._first_reads = self._first_reads, []
        stopped: List[int] = []
        with self._span("engine.first_tokens",
                        rows=sum(len(r[2]) for r in reads)):
            for first_dev, t_sent, rows in reads:
                # the blocking read alone, as the decode harvest's
                with self._span("engine.first_tokens.wait"):
                    # graftlint: ok[host-sync-hot-path] ONE read per prefill dispatch, after the next decode chunk is on the device
                    fp = np.asarray(first_dev)   # [2, bb]: tokens; lp bits
                toks = fp[0].tolist()
                lps = fp[1].view(np.float32).tolist()
                now = time.perf_counter()
                # per dispatch: prefill sent -> its first tokens on the host
                self.prefill_stats.add(now - t_sent)
                for col, state in rows:
                    req = state.request
                    state.first_pending = False
                    state.tokens.append(toks[col])
                    state.logprobs.append(lps[col])
                    state.produced = 1
                    state.first_token_at = now
                    self.ttft_stats.add(now - state.submitted_at)
                    state.stop_cut = find_stop_cut(state.tokens, req)
                    reason = "stop" if state.stop_cut >= 0 else "length"
                    if state.slot_id < 0:                   # never seated
                        self._finish_state(state, reason)
                    elif state.stop_cut >= 0:
                        # the chunk just dispatched carries the row: one
                        # chunk of one slot (a stop id decodes through it,
                        # an EOS came up inactive)
                        self._finishes_learned_late += 1
                        stopped.append(state.slot_id)
                        self._finish(state.slot_id, reason)
                    else:
                        self._emit_stream(state)
        self._deactivate_many(stopped)

    def _admit_row_cap(self) -> int:
        """Rows per admission-prefill dispatch: bounds the [L, bb, T,
        Hkv, Dh] x2 prefill-KV transient (config.admission_max_rows —
        the bb=128 transient OOMed 16 GB chips nondeterministically)."""
        cap = self.config.admission_max_rows
        return min(self.max_slots, cap) if cap else self.max_slots

    def _try_admit(self) -> int:
        """Prefill waiting requests into free slots; returns #admitted.

        Cache-miss admissions are BATCHED: every admittable waiting request
        shares one prefill program, one page write, and one state install
        (N serial admissions are N× the fixed dispatch cost).
        Prefix-cache hits run
        their suffix programs individually (per-hit context shapes).
        """
        self._shed_expired()
        if self._swapped:
            # swap-preempted sequences are OLDER than anything waiting:
            # they resume first, before new admissions drain the pool
            self._resume_swapped()
        admitted = self._admit_prefilled()
        # rows: (req, cb, slot, tokens-to-prefill, t_submit, full_prompt);
        # full_prompt is None for whole-prompt admissions, the complete
        # prompt for the FIRST CHUNK of a chunked admission (which rides
        # this same batched prefill instead of burning a batch=1 dispatch)
        batch: List[Tuple] = []
        # first-page hashes the CURRENT batch will register post-prefill:
        # a same-round request sharing one must wait for the flush (then
        # its alloc sees the registered pages and takes the suffix path)
        pending_hashes: set = set()
        while self._waiting:
            req, on_tok, t_submit = self._waiting[0]
            # overlong prompts keep their tail (sliding-window truncation,
            # same policy as Engine.generate); cap leaves ≥1 decode position
            prompt = req.prompt[-(self.max_seq_len - 1):]
            if self.prefix_cache:
                h1 = self.kv.first_page_hash(prompt)
                if batch and h1 is not None and h1 in pending_hashes:
                    self._admit_batch(batch)       # registers their pages
                    batch = []
                    pending_hashes.clear()
                got = self.kv.alloc_slot_prefix(prompt)
                if got is None:
                    self._admission_denied += 1
                    break
                slot, n_cached = got
            else:
                slot = self.kv.alloc_slot(len(prompt))
                n_cached = 0
                if slot is None:
                    self._admission_denied += 1
                    break
            # chunk whenever the UNCACHED portion exceeds the chunk — a
            # prefix-cache hit with a long unique tail stalls decode just
            # as hard as a cache miss
            will_chunk = (self._chunk
                          and len(prompt) - n_cached > self._chunk)
            if self.prefix_cache and n_cached == 0 and not will_chunk:
                # a chunked admission registers its prefix only after its
                # LAST chunk, many steps from now — advertising its hash
                # would trigger pointless flushes that register nothing
                hr = self.kv.first_page_hash(prompt, registerable=True)
                if hr is not None:
                    pending_hashes.add(hr)
            self._waiting.popleft()
            admitted += 1
            if will_chunk:
                # long uncached span: prefill incrementally between decode
                # chunks, resuming after any cached prefix
                if n_cached > 0:
                    self._prefix_hit_admissions += 1
                    self._start_chunked(req, on_tok, slot, prompt, t_submit,
                                        time.perf_counter(), done=n_cached)
                else:
                    # first chunk joins the batched admission prefill; the
                    # chunk advance takes over from there (done > 0 always)
                    batch.append((req, on_tok, slot, prompt[: self._chunk],
                                  t_submit, prompt))
                    if len(batch) >= self._admit_row_cap():
                        self._admit_batch(batch)
                        batch = []
                        pending_hashes.clear()
            elif n_cached > 0:
                admit = self._span("engine.admit", rows=1,
                                   prompt_tokens=len(prompt),
                                   request_ids=req.request_id)
                sp = self._dispatch_span("engine.prefill.dispatch", rows=1,
                                         prefill_tokens=len(prompt) - n_cached)
                self._rng, k0 = jax.random.split(self._rng)
                first_dev = self._prefill_cached_suffix(
                    prompt, slot, n_cached, req, k0)
                t_admit = time.perf_counter()
                self._tl_record(sp)
                self.kv.register_prefix(slot, prompt)
                self._total_prompt_tokens += len(prompt)
                self._seat([(req, on_tok, slot, len(prompt), t_submit,
                             t_admit, 0)], first_dev, sp.t0)
                admit.close()
            else:
                batch.append((req, on_tok, slot, prompt, t_submit, None))
                if len(batch) >= self._admit_row_cap():
                    self._admit_batch(batch)
                    batch = []
                    # flushed batches registered their pages — stale hashes
                    # here would force spurious flushes later this round
                    pending_hashes.clear()
        if batch:
            self._admit_batch(batch)
        return admitted

    def _admit_batch(self, batch) -> None:
        """One prefill + one page write + one install for N cache-miss
        admissions. Rows are padded to a power-of-two batch bucket; pad
        rows carry seq_len 0, so neither the page write nor the install
        touches anything (their page-table row points at page 0 but the
        valid mask drops every position)."""
        n = len(batch)
        bb = 1 << (n - 1).bit_length()                     # pow2 bucket
        tb = _next_bucket(max(len(p) for _, _, _, p, _, _ in batch),
                          self.prefill_buckets)
        with self._span("engine.admit", rows=n,
                        prompt_tokens=sum(len(b[3]) for b in batch),
                        request_ids=";".join(b[0].request_id
                                             for b in batch)):
            self._admit_rows(batch, n, bb, tb)

    def _admit_rows(self, batch, n: int, bb: int, tb: int) -> None:
        sp = self._dispatch_span("engine.prefill.dispatch", rows=n,
                                 prefill_tokens=sum(len(b[3])
                                                    for b in batch))
        self._prefill_calls += 1
        tokens, seq_dev, table_rows, sampling = self._padded_rows(
            bb, tb, [(b[3], b[0], b[2]) for b in batch])
        self._rng, k0 = jax.random.split(self._rng)
        self.kv.sync_tiers()           # flush host-tier traffic pre-write
        if self._prefill_pages is not None:
            # fused path: per-layer KV scatters into the donated pools
            # inside the prefill scan (pad rows' seq_len 0 drops every
            # position, exactly like the two-program path's write)
            slot_ids = None
            if self._family is not None:
                # pad rows point past the last slot: their state write drops
                slot_ids = np.full((bb,), self.max_slots, np.int32)
                slot_ids[:n] = [b[2] for b in batch]
                for row in batch:              # the prefill's key blocks
                    self._count(prefill_sums(
                        self.spec, len(row[3]), tb).items())
            first_dev, kp, vp, counters = self._prefill_pages(
                self.params, tokens, seq_dev, *self.kv.pools, table_rows,
                sampling, k0, slot_ids)
            if counters is not None:
                self._prefill_counters.append(counters)
        else:                      # sp: ring prefill returns stacked KV
            first_dev, ks, vs = self._prefill(
                self.params, tokens, seq_dev, sampling, k0)
            kp, vp = self._write_pages(
                self.kv.k_pages, self.kv.v_pages, ks, vs, table_rows,
                seq_dev)
        self.kv.swap(kp, vp)
        t_admit = time.perf_counter()    # slots held, prefill dispatched
        self._tl_record(sp, program=("prefill", bb, tb))
        rows: List[Tuple] = []
        for i, (req, cb, slot, prompt, t_submit, full) in enumerate(batch):
            if full is not None:
                # first chunk of a chunked admission: its KV pages are
                # written; the sample is discarded (the logits saw a
                # truncated prompt) and the parallel chunk advance takes
                # over. Prompt tokens/prefix registration are counted on
                # the LAST chunk.
                self._start_chunked(req, cb, slot, full, t_submit,
                                    t_admit, done=len(prompt))
                continue
            if self.prefix_cache:
                self.kv.register_prefix(slot, prompt)
            self._total_prompt_tokens += len(prompt)
            rows.append((req, cb, slot, len(prompt), t_submit, t_admit, i))
        self._seat(rows, first_dev, sp.t0)

    def _padded_rows(self, bb: int, tb: int, rows):
        """A prefill program's operands for ``rows`` of (tokens, request,
        slot), padded to ``bb`` rows of ``tb``: tokens [bb, tb], lengths
        [bb], the slots' page-table rows, the requests' sampling."""
        tokens = np.zeros((bb, tb), np.int32)
        lens = np.zeros((bb,), np.int32)
        table_rows = np.zeros((bb, self.kv.max_pages_per_seq), np.int32)
        temps = np.zeros((bb,), np.float32)
        top_k = np.zeros((bb,), np.int32)
        top_p = np.ones((bb,), np.float32)
        min_p = np.zeros((bb,), np.float32)
        for i, (toks, req, slot) in enumerate(rows):
            tokens[i, : len(toks)] = toks
            lens[i] = len(toks)
            table_rows[i] = self.kv._table[slot]
            temps[i] = req.temperature
            top_k[i] = req.top_k
            top_p[i] = req.top_p
            min_p[i] = req.min_p
        return (jnp.asarray(tokens), jnp.asarray(lens),
                jnp.asarray(table_rows),
                SamplingParams(jnp.asarray(temps), jnp.asarray(top_k),
                               jnp.asarray(top_p), jnp.asarray(min_p)))

    def _run_suffix_prefill(self, suffixes, slots, n_ctxs, reqs, key):
        """Run ONE jitted suffix-prefill over N partially prefilled
        sequences: row i's ``suffixes[i]`` continues ``n_ctxs[i]`` tokens
        (page-aligned) already sitting in ``slots[i]``'s pages, fresh KV is
        written at that offset, and the sampled next tokens come back as a
        [2, bb] device buffer (token row; logprob bits row). Shared by
        prefix-cache hits (N=1) and the parallel chunked-prefill advance
        (N = every in-flight long prompt — N serial dispatches were the
        round-1 serialization VERDICT item 7 calls out)."""
        n = len(suffixes)
        bb = 1 << (n - 1).bit_length()
        tb = _next_bucket(max(len(s) for s in suffixes),
                          self.prefill_buckets)
        mpb = _next_bucket(max(c // self.kv.page_size for c in n_ctxs),
                           self._ctx_page_buckets)
        tokens, lens_dev, table_rows, sampling = self._padded_rows(
            bb, tb, list(zip(suffixes, reqs, slots)))
        n_ctx = np.zeros((bb,), np.int32)
        n_ctx[:n] = n_ctxs
        ctx_dev = jnp.asarray(n_ctx)
        phys = np.zeros((bb, mpb), np.int32)
        phys[:n] = self.kv._table[list(slots), :mpb]
        # flush host-tier traffic: staged uploads (host prefix hits) must
        # land before the suffix program reads its context pages
        self.kv.sync_tiers()
        first_dev, ks, vs = self._prefill_suffix(
            self.params, tokens, lens_dev, ctx_dev, jnp.asarray(phys),
            self.kv.k_pages, self.kv.v_pages, sampling, key,
            n_ctx_pages=mpb,
        )
        kp, vp = self._write_pages(
            self.kv.k_pages, self.kv.v_pages, ks, vs, table_rows, lens_dev,
            start=ctx_dev,
        )
        self.kv.swap(kp, vp)
        return first_dev

    def _prefill_cached_suffix(self, prompt, slot: int, n_cached: int,
                               req, key):
        """Prefix-cache-hit admission: prefill only the uncached tail.
        ``n_cached`` is a whole number of pages and < len(prompt)
        (``PagedKVCache.alloc_slot_prefix``)."""
        self._prefix_hit_admissions += 1
        return self._run_suffix_prefill([prompt[n_cached:]], [slot],
                                        [n_cached], [req], key)

    # ----------------------------------------------------- chunked prefill

    def _start_chunked(self, req: GenerationRequest, on_tokens, slot: int,
                       prompt: List[int], t_submit: float, t_admit: float,
                       done: int = 0) -> None:
        """Begin incremental prefill of a long prompt: the slot and its
        pages are reserved now; chunks run one per engine step. ``done``
        > 0 resumes after a prefix-cache hit (page-aligned)."""
        self._chunked_admissions += 1
        prog = _PrefillProgress(req, prompt, on_tokens, t_submit, t_admit)
        prog.done = done
        self._prefilling[slot] = prog

    def _advance_chunked(self) -> None:
        """Advance EVERY in-flight chunked prefill by one chunk, in ONE
        batched suffix dispatch.

        Round 1 advanced one prompt per step (VERDICT item 7): a burst of
        N long prompts serialized — the Nth waited N×(prompt/chunk) steps
        with its slot and pages already reserved, and every suffix chunk
        ran a batch=1 program. Batching keeps the per-step decode stall
        bounded by ONE chunk's sequence length (the rows pad to a shared
        suffix bucket; extra rows add MXU work, not critical-path depth)
        while cutting a burst's total prefill steps by N× and its page
        idle-reservation time with it.

        Every entry has ``done > 0`` (first chunks ride the admission
        batch; prefix-hit resumes start at their cached length), so the
        advance is always the suffix program — one code path.

        Rows are grouped by context-page bucket: batching pads every row's
        context gather to the batch MAX bucket, so one nearly-finished
        long prompt would otherwise scale every row's dense ctx buffer and
        attention to its size — per-bucket groups bound the padding waste
        to <2× per row while keeping dispatches O(log) per step.
        """
        if not self._prefilling:
            return
        groups: Dict[int, List[Tuple[int, _PrefillProgress]]] = {}
        for slot, prog in self._prefilling.items():
            b = _next_bucket(prog.done // self.kv.page_size,
                             self._ctx_page_buckets)
            groups.setdefault(b, []).append((slot, prog))
        for _, items in sorted(groups.items()):
            self._advance_group(items)

    def _advance_group(self, items) -> None:
        """One batched suffix dispatch advancing ``items`` (same ctx-page
        bucket) by one chunk each; finishing rows become live slots."""
        suffixes = [prog.prompt[prog.done: prog.done + self._chunk]
                    for _, prog in items]
        sp = self._dispatch_span("engine.prefill.dispatch", rows=len(items),
                                 prefill_tokens=sum(len(s) for s in suffixes),
                                 chunk=True)
        self._rng, k0 = jax.random.split(self._rng)
        first_dev = self._run_suffix_prefill(
            suffixes, [slot for slot, _ in items],
            [prog.done for _, prog in items],
            [prog.request for _, prog in items], k0)
        self._prefill_calls += 1
        self._tl_record(sp)
        rows: List[Tuple] = []
        for i, (slot, prog) in enumerate(items):
            prog.done += len(suffixes[i])
            if prog.done < len(prog.prompt):
                continue
            del self._prefilling[slot]
            if self.prefix_cache:
                self.kv.register_prefix(slot, prog.prompt)
            self._total_prompt_tokens += len(prog.prompt)
            # only the LAST chunk's sample is the real first token (earlier
            # chunks' samples are discarded — their logits see a truncated
            # prompt)
            rows.append((prog.request, prog.on_tokens, slot,
                         len(prog.prompt), prog.t_submit, prog.t_admit, i))
        self._seat(rows, first_dev, sp.t0)

    # ---------------------------------------------------------- streaming

    def _emit_stream(self, state: _Slot) -> None:
        """Push newly generated tokens to the slot's streaming callback,
        trimmed exactly like ``_finish`` trims the final result (cap at
        max_new_tokens, cut after EOS) so a streaming consumer never sees
        tokens the result won't contain."""
        cb = state.on_tokens
        if cb is None:
            return
        req = state.request
        toks = state.tokens[: req.max_new_tokens]
        if 0 <= state.stop_cut <= len(toks):
            # cut found by the incremental scan (or first-token check) —
            # no rescan of the whole history per chunk
            toks = toks[: state.stop_cut]
        if len(toks) > state.streamed:
            fresh = toks[state.streamed:]
            state.streamed = len(toks)
            try:
                cb(fresh)
            except Exception:
                logger.exception("stream callback failed for %s",
                                 req.request_id)
                state.on_tokens = None     # don't retry a broken consumer

    # ------------------------------------------------------------- finish

    def _first_read_ended_it(self, slot: int, state: _Slot) -> bool:
        """A slot about to be retired before its first token was read
        (the capacity loop met it right after its admission): read the
        token now. True when that read already finished the request (the
        token was a stop), so the caller has nothing left to retire."""
        if state.first_pending:
            self._read_firsts()
        return self._slots.get(slot) is not state

    def _finish(self, slot: int, reason: str) -> None:
        state = self._slots[slot]
        if self._first_read_ended_it(slot, state):
            return
        del self._slots[slot]
        self._stop_slots.discard(slot)
        self._release_slot(slot)
        self._finish_state(state, reason)

    def _finish_state(self, state: _Slot, reason: str) -> None:
        """The result of a sequence that holds no slot any more."""
        req = state.request
        # a finishing sequence streams what it still holds at once, ahead
        # of its result: frames in token order, the final envelope last
        self._emit_stream(state)
        toks, stopped = trim_at_stops(state.tokens, req)
        if stopped:
            reason = "stop"
        self._total_generated += len(toks)
        logprobs = state.logprobs[: len(toks)]
        prompt_len = state.prompt_len
        t_submit, t_admit, t_first = (state.submitted_at, state.admitted_at,
                                      state.first_token_at)
        before = self._resumed.pop(req.request_id, None)
        if before is not None:
            # pre-empted and re-prefilled (_preempt_recompute): the result
            # is the ORIGINAL request's, its earlier tokens first
            toks = before["tokens"] + toks
            logprobs = before["logprobs"] + logprobs
            prompt_len = before["prompt_len"]
            t_submit, t_admit, t_first = before["stamps"]
        self._finished.append(GenerationResult(
            request_id=req.request_id,
            tokens=toks,
            finish_reason=reason,
            prompt_tokens=prompt_len,
            logprobs=logprobs,
            ttft_s=t_first - t_submit,
            decode_s=time.perf_counter() - t_first,
            stamps={"submitted": t_submit, "admitted": t_admit,
                    "first_token": t_first},
        ))

    # ---------------------------------------- re-prefill pre-emption

    def _preempt_recompute(self, slot: int) -> bool:
        """A sequence of a per-layer spec that cannot grow (the page pool
        is dry): give its slot and pages back and put it at the FRONT of
        the queue as prompt + the tokens it has produced, to be
        re-prefilled when pages free up. Pages and any recurrent state are
        rebuilt from the tokens; nothing ever resumes on a zero state, and
        swapping pages (kv_offload) is refused for such a spec at load.
        False when
        it should finish as "length" instead: at the model's cap, budget
        spent, or no other sequence is live to free a page."""
        state = self._slots[slot]
        req = state.request
        if self._first_read_ended_it(slot, state):
            return True
        total = state.prompt_len + len(state.tokens)
        left = req.max_new_tokens - len(state.tokens)
        if (left < 1 or state.stop_cut >= 0 or total >= self.max_seq_len - 1
                or len(self._slots) + len(self._prefilling) < 2):
            return False
        self._slots.pop(slot)
        self._stop_slots.discard(slot)
        self._release_slot(slot)
        first = self._resumed.get(req.request_id)
        if first is None:
            first = {"tokens": [], "logprobs": [],
                     "prompt_len": state.prompt_len,
                     "stamps": (state.submitted_at, state.admitted_at,
                                state.first_token_at)}
            self._resumed[req.request_id] = first
        first["tokens"] += state.tokens
        first["logprobs"] += state.logprobs
        again = dataclasses.replace(
            req, prompt=list(req.prompt) + list(state.tokens),
            max_new_tokens=left)
        self._waiting.appendleft((again, state.on_tokens,
                                  state.submitted_at))
        self._reprefill_preemptions += 1
        return True

    # ------------------------------------------------- swap-based preempt

    def _try_swap_out(self, slot: int) -> bool:
        """Preempt a decode slot that cannot grow: park its exact KV on
        the host tier and queue it for a later resume, instead of the
        discard-only ``finish_reason="length"``. Returns False when the
        slot should finish normally (budget/stop already reached, or at
        the model cap, or the host tier refuses the bytes)."""
        if self._offload is None:
            return False
        state = self._slots[slot]
        req = state.request
        cur = int(self._lengths_host[slot])
        if cur >= self.max_seq_len:
            return False                 # model cap: "length" is correct
        if self._first_read_ended_it(slot, state):
            return True
        if state.produced >= req.max_new_tokens or state.stop_cut >= 0:
            return False                 # already done — plain finish
        n_pages = self.kv._pages_for(cur)
        nbytes = n_pages * self.kv.page_bytes
        if not self._offload.reserve_swap(nbytes):
            self._swap_fallbacks += 1
            return False
        pages = self.kv._slot_pages[slot][:n_pages]
        ks, vs = self.kv.read_pages(pages)   # one batched device→host read
        self._swapped.append(_SwapRecord(state, cur, ks, vs, nbytes))
        self._slots.pop(slot)
        self._release_slot(slot)
        self._swap_outs += 1
        return True

    def _resume_swapped(self) -> int:
        """Re-admit parked sequences (FIFO) once a slot AND one decode
        chunk's worth of page headroom are free — the headroom gate keeps
        a resume from being immediately re-preempted. Resume is an
        install + staged page upload: NO prefill program runs (the
        acceptance invariant ``prefill_calls`` counts)."""
        resumed = 0
        n_steps = self.config.decode_steps_per_call
        while self._swapped:
            rec = self._swapped[0]
            need = self.kv._pages_for(
                min(rec.kv_len + n_steps, self.max_seq_len))
            if not self.kv._free_slots or self.kv.available_pages < need:
                if not self._slots and not self._prefilling:
                    # idle engine that still can't host the record (pool
                    # smaller than the sequence): nothing will ever free
                    # more — finish it rather than spin forever
                    self._swapped.popleft()
                    self._finish_swapped(rec, "length")
                    continue
                break
            slot = self.kv.alloc_slot(rec.kv_len)
            if slot is None:
                break
            self._swapped.popleft()
            pages = self.kv._slot_pages[slot]
            self.kv.stage_uploads(pages[: len(rec.k_pages)],
                                  rec.k_pages, rec.v_pages)
            self._offload.release_swap(rec.nbytes)
            state = rec.state
            state.slot_id = slot
            self._slots[slot] = state
            req = state.request
            # device install: KV holds exactly kv_len positions and the
            # last sampled token is tokens[-1] — the same (lengths, last)
            # contract a fresh admission meets, so the ordinary install
            # program applies. TTFT was stamped long ago; no re-stamp.
            self._install_device([{
                "slot": slot, "prompt_len": rec.kv_len,
                "first": state.tokens[-1], "max_new": req.max_new_tokens,
                "eos": req.eos_id, "temp": req.temperature,
                "top_k": req.top_k, "top_p": req.top_p,
                "min_p": req.min_p,
                "stops": list(req.stop_ids or ())[:_DEVICE_STOP_K]}])
            # _install hard-codes produced=1 (true for admissions);
            # restore the real count — rare path, eager set acceptable
            self._produced = self._produced.at[slot].set(state.produced)
            self._swap_resumes += 1
            resumed += 1
        return resumed

    def _finish_swapped(self, rec: _SwapRecord, reason: str) -> None:
        """Resolve a parked sequence without resuming it (engine-idle
        fallback and abort paths); releases its host reservation."""
        self._offload.release_swap(rec.nbytes)
        state = rec.state
        req = state.request
        toks, stopped = trim_at_stops(state.tokens, req)
        if stopped:
            reason = "stop"
        self._total_generated += len(toks)
        self._finished.append(GenerationResult(
            request_id=req.request_id,
            tokens=toks,
            finish_reason=reason,
            prompt_tokens=state.prompt_len,
            logprobs=state.logprobs[: len(toks)],
            ttft_s=state.first_token_at - state.submitted_at,
            decode_s=time.perf_counter() - state.first_token_at,
            stamps={"submitted": state.submitted_at,
                    "admitted": state.admitted_at,
                    "first_token": state.first_token_at},
        ))

    def prefetch_probe(self, request: GenerationRequest) -> int:
        """Async-prefetch hook for the serving layer: on enqueue, hash the
        request's (clamped) prompt and start host→device uploads for any
        leading pages resident only in the host tier — the PCIe copy then
        overlaps queue wait and batch formation instead of sitting on the
        admission critical path. Safe no-op without the offload tier."""
        if self._offload is None or not self.prefix_cache:
            return 0
        prompt = request.prompt[-(self.max_seq_len - 1):]
        matchable = (len(prompt) - 1) // self.kv.page_size
        if matchable < 1:
            return 0
        hashes = page_chain_hashes(prompt, matchable, self.kv.page_size)
        return self.kv.prefetch_chain(hashes)

    def kv_export(self, tokens, max_pages: int = 0):
        """Serialize the longest locally-resident full-page prefix of
        ``tokens`` as a KV-fabric wire dict (``engine/kv_fabric.py``), or
        None when nothing is resident. Cold path — drain handoff and
        coordinator pre-warm pulls, never the decode loop."""
        if self._recurrent:
            raise ValueError("kv_export: pages without the recurrent state "
                             "at their end are not a prefix of this spec")
        if self._per_layer:
            raise ValueError("kv_export: a per-layer spec has no prefill "
                             "that continues from cached pages, so no "
                             "worker could use an exported prefix")
        if not self.prefix_cache:
            return None
        from .kv_fabric import export_paged_kv

        prompt = list(tokens)[-(self.max_seq_len - 1):]
        return export_paged_kv(self.kv, prompt, max_pages=max_pages)

    def kv_import(self, wire) -> int:
        """Validate a KV-fabric wire against the local pool, land its
        pages in the HOST tier, and start the layer-wise host→device
        restage. Returns pages newly stored. Raises ``FabricRejected``
        with NOTHING stored on any mismatch — the caller falls back to
        normal prefill, never serves wrong KV."""
        from .kv_fabric import FabricRejected, import_paged_kv

        if not self.prefix_cache or self._offload is None:
            raise FabricRejected(
                "worker has no prefix cache / host KV tier")
        stored = import_paged_kv(self.kv, wire)
        # kick the async restage now: per-layer staged device_puts overlap
        # whatever the engine does until an admission consumes them (the
        # prefetch-on-admit pump re-kicks for requests that arrive later)
        self.kv.prefetch_chain([pg["hash"] for pg in wire.get("pages", [])])
        return stored

    # --------------------------------------------------------------- step

    def _run_overlap_hook(self) -> None:
        """Invoke the serving layer's overlap hook (see ``__init__``) —
        exceptions are logged, never fatal to the step."""
        hook = self.overlap_hook
        if hook is None:
            return
        try:
            hook()
        except Exception:
            logger.exception("overlap hook failed")

    def _span(self, name: str, **args: Any) -> HostSpan:
        """Open a host span of the engine thread (``obs.timeline``)."""
        return host_span(self.timeline, name, **args)

    def _dispatch_span(self, name: str, **args: Any) -> HostSpan:
        """Open a device-dispatch bracket; ``_tl_record`` closes it."""
        return host_span(self.timeline, name, dispatch=True, **args)

    def _tl_record(self, span: HostSpan, **args: Any) -> None:
        """Close a dispatch bracket: its annotation, its ring record (none
        when the ring is disabled) and the dispatch/gap accounting.

        ``program`` (optional) is the program-shape key it ran. Occupancy
        args are read from cheap host mirrors so the hot path stays
        unmetered between scrapes."""
        args["live_slots"] = len(self._slots)
        args["waiting"] = len(self._waiting)
        if self._prefilling:
            args["prefilling"] = len(self._prefilling)
        if self._swapped:
            args["swapped"] = len(self._swapped)
        if self.timeline is not None:
            kv = self.kv
            args["kv_pages_used"] = (kv.num_pages - len(kv._free)
                                     - len(kv._reclaimable))
            args["kv_pages_total"] = kv.num_pages
            if kv.offload is not None:
                args["host_pages"] = kv.offload.get_stats().get(
                    "host_pages", 0)
        t0 = span.t0
        now = span.close(**args)
        # dispatch/gap accounting runs even with the ring disabled: the
        # roofline split (bench.py) and the engine_host_* metric families
        # depend on it, and it is two float adds per dispatch
        self._dispatch_s += now - t0
        if self._last_dispatch_end is not None:
            gap = t0 - self._last_dispatch_end
            if gap > 0:
                self._host_gap_s += gap
        self._last_dispatch_end = now

    def _close_dispatch(self) -> None:
        """Close the step's ``engine.decode.dispatch`` bracket, once: at
        the end of its blocking packed read (or of the step, when nothing
        was read), so ``host_gap_s_total`` counts all the host's time from
        there to the next dispatch."""
        if self._open_dispatch is not None:
            (sp, args), self._open_dispatch = self._open_dispatch, None
            self._tl_record(sp, **args)

    @hot_path
    def step(self) -> int:
        """One engine iteration, the engine's ONE sequence: hand on the
        slots of the rows certain to end in the chunk in flight; admit and
        dispatch prefills (first tokens stay on the device); advance one
        prefill chunk; dispatch decode chunk k+1; THEN read chunk k's
        packed output, append, judge, stream; then read the new
        admissions' first tokens from their prefills' own outputs. All of
        the host's bookkeeping runs while chunk k+1 is on the device;
        finishes the host cannot foresee (EOS, stops, a row paused at its
        grant) and host-side stops are learned one chunk behind the
        device. Where the page pool cannot back a chunk ahead of the one in
        flight, the iteration reads chunk k BEFORE it dispatches and
        decides on current lengths. Returns live + mid-prefill slots after
        the iteration."""
        # one span over the whole iteration: the admission scan and the
        # capacity loop run before any bracket below opens
        with self._span("engine.step"):
            return self._step()

    @hot_path
    def _step(self) -> int:
        self._hand_on_foreseen()
        self._try_admit()
        self._advance_chunked()
        if self._slots:
            self._steps += 1
            self._occupancy_sum += len(self._slots)   # batch occupancy metric
            n_steps = self._reserve_chunk()
            if n_steps is None:
                # the pool cannot back a chunk AHEAD of the one in flight:
                # read that one first and decide on current lengths (its
                # finishes may free the pages; a swap or a re-prefill
                # needs the current tokens)
                self._sync_fallback_iterations += 1
                self._read_pending()
                n_steps = self._reserve_chunk()
        if not self._slots:
            # nothing to dispatch. What the chunk in flight still owes
            # (handed-on rows' last tokens) is read now; with none of
            # those every row of its snapshot is already finished and it
            # is dropped unread, device buffer and _Slot references
            if self._pending is not None and not self._pending.handed_on:
                self._pending = None
            self._read_pending()
            self._read_firsts()
            return self.n_live

        # adaptive chunk length (ISSUE 13): while ANY live slot is
        # streaming, decode in shorter chunks so tokens reach the host
        # every stream_chunk_steps instead of every full megastep.
        # Pow2-bucketed so the whole run adds at most ONE
        # decode program per (bucket, ctx) pair — the compile-count guard
        # in tests/test_streaming.py audits this. Pure-batch rounds keep
        # the full chunk: the clamp looks at live callbacks, not config.
        scs = int(getattr(self.config, "stream_chunk_steps", 0) or 0)
        if scs > 0 and n_steps > 1 and any(
                s.on_tokens is not None for s in self._slots.values()):
            sub = 1 << (scs - 1).bit_length()
            if sub < n_steps:
                n_steps = sub
                self._stream_clamped_chunks += 1

        if self.kv.n_free_slots and not self.n_waiting:
            self._empty_slot_dispatches += 1
        sp = self._dispatch_span("engine.decode.dispatch", steps=n_steps,
                                 live_slots=len(self._slots))
        cap_list = [min(self.kv.slot_capacity(s), self.max_seq_len)
                    if s in self._slots else 0
                    for s in range(self.max_slots)]
        cap = jnp.asarray(cap_list, jnp.int32)
        mpb = 0
        if self.body == "dense":
            # dense working buffer covers the longest LIVE prefix, padded
            # to a pow2 page bucket (one compiled chunk per bucket) — NOT
            # max_pages_per_seq, so short-context rounds read short
            # buffers. The mirror is as old as the chunk in flight: pad by
            # its worst-case growth.
            mx = max(int(self._lengths_host[s]) for s in self._slots)
            if self._pending is not None:
                mx = min(mx + self._pending.n_steps, self.max_seq_len)
            mpb = _next_bucket(-(-mx // self.kv.page_size),
                               self._ctx_page_buckets)
        sampling = SamplingParams(self._temps, self._top_k, self._top_p,
                                  self._min_p)
        self._rng, kc = jax.random.split(self._rng)
        # flush host-tier traffic (evict-offload reads queued by the
        # capacity loop's reclaims; swap-in uploads staged by resume)
        # before the chunk writes the pools
        self.kv.sync_tiers()
        self.kv.tally_window()
        carry, packed = self._decode_chunk(
            self.params, *self.kv.pools,
            self._lengths, self._last, self._active, self._produced,
            self.kv.page_table, cap, self._max_new, sampling, self._eos,
            self._stops_dev, kc, n_steps=n_steps,
            n_ctx_pages=mpb, use_stops=bool(self._stop_slots),
        )
        kp, vp, self._lengths, self._last, self._active, self._produced = carry
        self.kv.swap(kp, vp)
        self._decode_chunks += 1
        # start the packed output's device→host copy: by the time the
        # NEXT iteration reads it the bytes are usually already host-side
        start = getattr(packed, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:   # pragma: no cover - backend quirk
                pass
        # the chunk is in flight: overlap serving-side batch formation
        # with the device step (ISSUE 5c)
        self._run_overlap_hook()

        # snapshot at dispatch: packed columns belong to THESE _Slot
        # objects — a slot freed and re-admitted before this chunk is
        # read must not have the old chunk's column applied to it
        snapshot = dict(self._slots)
        self._open_dispatch = (sp, {"program": ("decode", n_steps, mpb),
                                    "rows": len(snapshot),
                                    "n_steps": n_steps})
        prev, self._pending = self._pending, _ChunkEntry(
            packed, n_steps, snapshot, cap_list)
        if prev is not None:
            self._process_packed(prev)
        self._read_firsts()
        self._close_dispatch()      # nothing was read (the first chunk)
        return self.n_live

    def _hand_on_foreseen(self) -> None:
        """Take out of the slot map every row that is CERTAIN to be
        inactive when the chunk in flight ends, and free its slot, pages
        and recurrent state row for a successor now: device order makes it
        safe (whatever takes them is queued behind that chunk). The host
        is one chunk behind, but a live row emits every step, so it knows
        which rows reach ``max_new_tokens`` (or the model's
        ``max_seq_len``) inside the chunk — provided the capacity the chunk
        ran with covers those steps: a row the device paused at its grant
        has produced less than the host reckons, and is learned at the
        read. A row that stops sooner (EOS, a stop) ends in the chunk all
        the same. The chunk's own read delivers the old request's last
        tokens and its result from the entry's snapshot
        (``_judge_packed``)."""
        entry = self._pending
        if entry is None:
            return
        for slot, state in entry.snapshot.items():
            if (self._slots.get(slot) is not state or slot in entry.idle
                    or state.first_pending):
                continue
            start = int(self._lengths_host[slot])    # as the chunk began
            left = state.request.max_new_tokens - state.produced
            cap = entry.caps[slot]
            if ((left <= entry.n_steps and start + left <= cap)
                    or (start + entry.n_steps >= cap >= self.max_seq_len)):
                del self._slots[slot]
                self._stop_slots.discard(slot)
                self._release_slot(slot)
                entry.handed_on.add(slot)

    def _reserve_chunk(self) -> Optional[int]:
        """The capacity loop: grow every live slot to hold one more chunk
        BEYOND what the chunk in flight can bring it to (the host's
        lengths are that chunk's starting ones), and one row more, so that
        a grant never ends exactly where a chunk does (the device pauses a
        row AT its grant, and under a chunk in flight a pause costs the row
        the next chunk). Returns the steps the next chunk may run, or None
        when a slot's want cannot be met while a chunk is in flight: the
        caller reads that chunk and calls again. With none in flight,
        lengths are current and a slot that cannot fit one more token is
        re-queued, swapped out or finished (pool pressure or cap)."""
        n_steps = full = self.config.decode_steps_per_call
        pending = self._pending
        retired: List[int] = []
        for slot in list(self._slots):
            state = self._slots.get(slot)
            if state is None:
                continue       # a first token read in this loop ended it
            cur = int(self._lengths_host[slot])
            # a sliding layer's pages the window has wholly passed go back
            # to their free list first (no-op for a spec without them)
            self.kv.release_behind_window(slot, cur)
            if (pending is not None and slot not in pending.idle
                    and pending.snapshot.get(slot) is state):
                # the most it can hold when the chunk in flight ends
                cur = min(cur + pending.n_steps, pending.caps[slot])
            want = min(cur + full + 1, self.max_seq_len)
            cap_tok = self.kv.ensure_capacity(slot, want)
            if pending is not None and (cap_tok < want or cap_tok <= cur):
                return None
            if cap_tok <= cur:
                if self._per_layer and self._preempt_recompute(slot):
                    retired.append(slot)       # re-queued, no finish
                elif self._try_swap_out(slot):
                    retired.append(slot)       # deactivate, no finish
                else:
                    self._capacity_finishes += 1
                    retired.append(slot)
                    self._finish(slot, "length")
            else:
                n_steps = min(n_steps, cap_tok - cur)
        self._deactivate_many(retired)
        return n_steps

    def _read_pending(self) -> None:
        """Read and process the chunk in flight now, if there is one."""
        prev, self._pending = self._pending, None
        if prev is not None:
            self._process_packed(prev)

    def _count(self, named) -> None:
        """Add ``(name, n)`` pairs to the counters (no name: no reader)."""
        for name, n in named:
            if name:
                self._counters[name] += n

    def _harvest_chunk(self, entry: _ChunkEntry
                       ) -> Tuple[np.ndarray, Dict[int, bool], bool]:
        """TOKEN half of chunk processing: the blocking host read (short
        or none when the chunk ended while the host was busy: its async
        copy has landed), the length-mirror refresh, token and logprob
        appends and the incremental stop scan. Returns the packed rows,
        slot -> "its row emitted in this chunk", and whether a slot with a
        stream callback was met. Snapshot-identity rule: a column applies
        only to the exact ``_Slot`` object live at dispatch, still in its
        slot or handed on in this entry."""
        n_steps = entry.n_steps
        wait = self._span("engine.harvest.wait")     # the blocking read alone
        # graftlint: ok[host-sync-hot-path] THE designed sync point: ONE packed read per decode chunk carries tokens+lps+active+lengths
        packed_np = np.asarray(entry.packed)   # ONE blocking read per chunk
        # the residue the overlap failed to hide; near zero means the
        # host came back to a chunk that had already ended
        waited = wait.close() - wait.t0
        self.chunk_stats.add(waited)
        self._harvest_wait_s += waited
        self._close_dispatch()
        toks_np = packed_np[:n_steps]                    # [n_steps, max_slots]
        lps_np = packed_np[n_steps:2 * n_steps].view(np.float32)
        lengths = packed_np[2 * n_steps + 1].tolist()
        self._decode_steps += n_steps
        book = self._span("engine.harvest.book")  # mirror, appends, stops
        # a row emits from step 0 until it goes inactive and never again
        # in the chunk (_advance), so its tokens are a PREFIX of its
        # column: one count a slot, the columns as lists in one call each
        counts_np = (toks_np >= 0).sum(axis=0)
        counts = counts_np.tolist()
        if self._family is not None:
            chunk = dict(zip(self._family.DECODE_COUNTERS,
                             packed_np[2 * n_steps + 2:, 0].tolist()),
                         **decode_sums(self.spec, counts_np,
                                       packed_np[2 * n_steps + 1]))
            # the one counter a decode chunk feeds twice: its assignments
            # on held experts are the whole run's (prefills add theirs) and
            # the decode steps' own
            if "moe.assignments_held" in chunk:
                chunk["moe.decode_assignments_held"] = chunk[
                    "moe.assignments_held"]
            self._count(chunk.items())
            # the counters of the prefills dispatched since the last
            # harvest. One admitted in THIS step runs behind the chunk just
            # read: its read blocks until it ends, a wait under a span of
            # its own, as the first tokens' is
            while self._prefill_counters:
                with self._span("engine.prefill_counters.wait"):
                    # graftlint: ok[host-sync-hot-path] 3 ints a prefill dispatch, read where the host has nothing else to do before that prefill's first tokens
                    done = np.asarray(self._prefill_counters.pop()).tolist()
                self._count(zip(self._family.PREFILL_COUNTERS, done))
        tok_cols = toks_np.T.tolist()
        lp_cols = lps_np.T.tolist()
        progressed: Dict[int, bool] = {}
        streamed = False
        for slot, state in entry.snapshot.items():
            if state.first_pending:
                self._read_firsts()      # its first token goes first
            handed = slot in entry.handed_on
            if not handed:
                if self._slots.get(slot) is not state:
                    continue             # finished earlier (or slot reused)
                self._lengths_host[slot] = lengths[slot]
            n = counts[slot]
            # no progress == the row was device-INACTIVE when this chunk
            # was dispatched (an active row always emits >=1 token a
            # chunk: the capacity loop guarantees cap > length at
            # dispatch): a row paused at its grant whose revive landed
            # after this chunk was sent. Its judgment was the pausing
            # chunk's; this chunk's caps row is from AFTER the pool grew
            # and would misread the pause as a finished "length".
            progressed[slot] = bool(n)
            prev = len(state.tokens)           # first index not yet stop-checked
            state.tokens += tok_cols[slot][:n]
            state.logprobs += lp_cols[slot][:n]
            state.produced = len(state.tokens)
            req = state.request
            has_stops = (req.eos_id >= 0 or req.stop_ids
                         or req.stop_sequences)
            if has_stops and state.stop_cut < 0:
                # scan only the new window: O(total) stop detection across
                # a generation, shared with the streaming emit
                state.stop_cut = find_stop_cut(state.tokens, req, start=prev)
            streamed = streamed or state.on_tokens is not None
        book.close()
        return packed_np, progressed, streamed

    def _process_packed(self, entry: _ChunkEntry) -> None:
        """Process a chunk's packed output, the next chunk (if any)
        already on the device: the token half, then the CONTROL half —
        finish what ended, retire host-side stops, revive capacity-paused
        slots — then the streaming of the slots that live on."""
        packed_np, progressed, streamed = self._harvest_chunk(entry)
        with self._span("engine.process_packed"):
            self._judge_packed(entry, packed_np, progressed)
            if streamed:
                self._emit_chunk(entry)

    def _emit_chunk(self, entry: _ChunkEntry) -> None:
        """Stream the entry's fresh tokens for the slots that live on
        (a finishing one streamed its own from ``_finish_state``). The
        chunk counts once: as carried when the callbacks, and the
        event-loop thread they wake, run in the shadow of a chunk in
        flight, else as flushed (also when only finishing slots had
        anything to stream)."""
        live = [st for slot, st in entry.snapshot.items()
                if st.on_tokens is not None
                and self._slots.get(slot) is st
                and len(st.tokens) > st.streamed]
        hidden = bool(live) and self._pending is not None
        if hidden:
            self._emit_carried_chunks += 1
        else:
            self._emit_flushed_chunks += 1
        if live:
            with self._span("engine.emit.carried" if hidden
                            else "engine.emit.flushed", slots=len(live)):
                for state in live:
                    self._emit_stream(state)

    def flush_stream(self) -> None:
        """Deliver every token the device has produced and the host has
        not read: the chunk in flight (a blocking read of at most one
        chunk; its finishes among them) and the first tokens of prefills
        not read yet. For callers that stop driving ``step()`` with
        slots live (the pump on shutdown)."""
        self._read_pending()
        self._read_firsts()

    def _judge_packed(self, entry: _ChunkEntry, packed_np: np.ndarray,
                      progressed: Dict[int, bool]) -> None:
        """The judgments of ``_process_packed``, on a harvested entry."""
        n_steps = entry.n_steps
        caps = entry.caps
        active_np = packed_np[2 * n_steps].astype(bool)
        lengths_row = packed_np[2 * n_steps + 1].astype(np.int32)

        stop_retired: List[int] = []
        revived: List[int] = []
        for slot, state in entry.snapshot.items():
            if slot in entry.handed_on:
                # its slot went on while the chunk ran: the result leaves
                # here (_finish_state upgrades the reason to "stop" when a
                # stop condition is inside the cap)
                assert not active_np[slot], "a handed-on row outlived its chunk"
                self._finish_state(state, "length")
                continue
            if self._slots.get(slot) is not state:
                continue                 # finished earlier (or slot reused)
            req = state.request
            if not active_np[slot]:
                if not progressed.get(slot, False):
                    # inactive for the WHOLE chunk: pause/finish was (or
                    # will be) decided by the chunk that actually stopped
                    # it; nothing to judge here
                    pass
                elif (state.produced < req.max_new_tokens
                        and state.stop_cut < 0
                        and int(lengths_row[slot]) >= caps[slot]
                        and caps[slot] < self.max_seq_len):
                    # the device stopped at the chunk's CAPACITY grant, not
                    # at a budget or stop condition: the slot is paused,
                    # not finished. Revive it — the next capacity loop
                    # grows its pages (or retires it for real if the pool
                    # is dry). A slot already granted max_seq_len is NOT
                    # paused — no revive can grow it past the model cap,
                    # so it falls through to the "length" finish below
                    # instead of burning one more dispatch to learn the
                    # same.
                    revived.append(slot)
                else:
                    # _finish re-trims and upgrades the reason to "stop"
                    # when a stop condition is inside the cap
                    self._finishes_learned_late += 1
                    self._finish(slot, "length")
            elif ((req.stop_ids or req.stop_sequences)
                  and 0 <= state.stop_cut <= req.max_new_tokens):
                # host-side stops (multi-id / multi-token): the device loop
                # only knows eos_id, so retire the slot here
                self._finishes_learned_late += 1
                stop_retired.append(slot)
                self._finish(slot, "stop")
        self._deactivate_many(stop_retired)
        self._set_active(revived, True)
        if self._pending is not None:
            # the chunk in flight was sent before the revive: they sit it out
            self._pending.idle.update(revived)

    def _deactivate_many(self, slots: List[int]) -> None:
        """Clear retired slots' device active flags in ONE dispatch — a
        chunk that retires several slots must not pay one eager .at[].set
        round trip per slot (ADVICE r1), matching the one-dispatch-per-
        round discipline of ``_install_device``."""
        self._set_active(slots, False)

    def _set_active(self, slots: List[int], value: bool) -> None:
        """Set the device active flag of ``slots`` through a [max_slots]
        mask: ONE program whatever the count. An eager ``.at[idx].set``
        compiles anew for every new length of ``idx``, on the engine
        thread, at whatever moment traffic first retires or revives that
        many rows at once (``setup.backend_compiles_in_window``, PERF.md
        §6 PR 24/25); ``warmup()`` runs this once so the program exists
        before traffic."""
        if not slots:
            return
        mask = np.zeros((self.max_slots,), bool)
        mask[slots] = True
        # outside every dispatch bracket: a span of its own, so a compile
        # here (``compiles_after_warmup``) says where it ran
        with self._span("engine.set_active", rows=len(slots)):
            self._active = jnp.where(jnp.asarray(mask), value, self._active)

    # ---------------------------------------------------------------- run

    def run_until_idle(self, max_iters: int = 100000) -> List[GenerationResult]:
        """Pump until every queued request finishes; returns (and clears)
        the finished results."""
        for _ in range(max_iters):
            if self.step() == 0 and not self.n_waiting:
                break
        return self.drain_finished()

    def generate(self, requests: List[GenerationRequest]) -> List[GenerationResult]:
        """Engine-interface adapter (same contract as ``Engine.generate``):
        submit all, pump to completion, return in request order.

        With ``max_waiting`` set, requests past the cap come back as
        per-request ``finish_reason="overloaded"`` results — raising
        mid-batch would strand the already-submitted head of the batch in
        the queue, to be pumped later with nobody collecting the results
        (r3 review finding)."""
        order: List[str] = []
        shed: Dict[str, GenerationResult] = {}
        for r in requests:
            try:
                order.append(self.submit(r))
            except EngineOverloadedError as e:
                rid = r.request_id or f"creq-shed-{self._rejected_full}"
                r.request_id = rid
                order.append(rid)
                shed[rid] = GenerationResult(
                    request_id=rid, tokens=[], finish_reason="overloaded",
                    prompt_tokens=len(r.prompt),
                    metadata={"overload_reason": e.reason})
        results = {r.request_id: r for r in self.run_until_idle()}
        results.update(shed)
        return [results[i] for i in order]

    def drain_finished(self) -> List[GenerationResult]:
        out, self._finished = self._finished, []
        return out

    def abort_all(self) -> int:
        """Drop every waiting and live request (no results produced) and
        return their pages to the pool; returns how many. Recovery hook
        for the pump when a decode step fails irrecoverably. What the
        device had finished before is read first, where it still can be."""
        try:
            # tokens the device has already produced are delivered: a
            # handed-on slot's last ones and its result among them
            self.flush_stream()
        except Exception:
            logger.exception("abort_all: the chunk in flight was lost")
            self._pending = None
            self._first_reads.clear()
        n = (len(self._waiting) + len(self._waiting_prefilled)
             + len(self._slots) + len(self._prefilling)
             + len(self._swapped))
        self._open_dispatch = None      # the failed step's bracket
        self._waiting.clear()
        self._waiting_prefilled.clear()
        self._resumed.clear()           # nothing is left to resume
        self._prefill_counters.clear()
        while self._swapped:            # release their host reservations
            self._offload.release_swap(self._swapped.popleft().nbytes)
        for slot in list(self._slots):
            self._slots.pop(slot)
            self.kv.free_slot(slot)
        for slot in list(self._prefilling):
            self._prefilling.pop(slot)
            self.kv.free_slot(slot)
        self._active = jnp.zeros_like(self._active)
        if self.timeline is not None:
            # the failed step never closed its spans
            self.timeline._open.clear()
        return n

    @property
    def n_waiting(self) -> int:
        return len(self._waiting) + len(self._waiting_prefilled)

    @property
    def n_live(self) -> int:
        # mid-chunked-prefill sequences hold slots/pages and need further
        # step() calls: callers gating their pump loop on n_live (e.g.
        # serving/pump.py) must see them or the engine stalls mid-prompt;
        # swap-preempted sequences likewise — they resume via step()
        return len(self._slots) + len(self._prefilling) + len(self._swapped)

    # ------------------------------------------------------------- warmup

    def warmup(self, batch: Optional[int] = None,
               max_new_tokens: int = 2) -> int:
        """Pre-compile the serving programs: one rolling batch per
        (admission batch bucket × prefill bucket) — admission prefills pad
        to power-of-two batch buckets, so every occupancy a real burst can
        produce gets its program (``batch`` restricts to one bucket, same
        contract as the sibling engines). The prefix cache is DISABLED for
        the duration (and nothing registers): warmup prompts would
        otherwise alias each other — across rounds, and unavoidably on
        small vocabularies — collapsing batched admissions into
        cached-suffix hits and leaving those programs cold. The paged
        pools are fixed-shape, so the decode chunk compiles once; pages
        and slots are fully returned afterwards. Stat counters do tick.
        Each round is one ``engine.warmup.round`` span
        (``_close_warmup_round``). Returns the number of warmup rounds."""
        runs = 0
        if batch:
            sizes = [batch]
        else:
            bb = 1
            sizes = []
            while bb < self.max_slots:
                sizes.append(bb)
                bb *= 2
            sizes.append(self.max_slots)
            # a capped admission never dispatches a wider batch bucket
            cap = self._admit_row_cap()
            sizes = [n for n in sizes if n <= cap] or [cap]
        saved_prefix = self.prefix_cache
        saved_cap = self.config.max_waiting
        self.prefix_cache = False
        # warmup submits whole batch buckets at once — compile priming must
        # not trip the serving admission cap (found by the serving-sweep
        # smoke test: max_waiting < max_slots rejected its own warmup)
        self.config.max_waiting = 0
        try:
            for n in sizes:
                for tb in self.prefill_buckets:
                    prompt_len = min(tb,
                                     self.max_seq_len - 1 - max_new_tokens)
                    if prompt_len < 1:
                        continue
                    mark = compile_cache.log_index()
                    sp = self._span("engine.warmup.round", batch=n,
                                    bucket=tb)
                    if not runs and not self._slots:
                        # compile the active-flag update with the first
                        # round (no slot is live: a no-op)
                        self._set_active([0], False)
                    for _ in range(n):
                        self.submit(GenerationRequest(
                            prompt=[1] * prompt_len,
                            max_new_tokens=max_new_tokens))
                    self.run_until_idle()
                    runs += 1
                    self._close_warmup_round(sp, mark, n, tb)
        finally:
            self.prefix_cache = saved_prefix
            self.config.max_waiting = saved_cap
            self._warm_log_index = compile_cache.log_index()
            self._warm_counters = compile_cache.compile_counters()
        return runs

    def _close_warmup_round(self, span: HostSpan, mark: int, batch: int,
                            bucket: int) -> None:
        """End one round of the grid: its wall time split by the compile
        log's records since ``mark`` into ``trace_s``, ``lower_s``,
        ``compile_s`` (of which ``cache_retrieval_s`` read the persistent
        cache) and ``run_s`` = the rest, the programs actually running.
        jax traces, lowers and compiles on the thread that calls the
        program, the engine thread here, so the four add up to the round's
        wall time. Kept for ``get_metrics()["warmup"]`` and written on the
        round's ring record."""
        records, _ = compile_cache.compile_log(mark)
        parts = compile_cache.log_summary(records)
        wall = time.perf_counter() - span.t0
        parts["run_s"] = wall - (parts["trace_s"] + parts["lower_s"]
                                 + parts["compile_s"])
        span.close(**parts)
        self._warmup_rounds.append(
            {"batch": batch, "bucket": bucket, "wall_s": wall, **parts})

    def warmup_from_manifest(self, max_new_tokens: int = 2) -> int:
        """Artifact-aware warmup: prime only the admission batch buckets
        the artifact's writer recorded, so a respawned worker warms what
        its predecessor actually served instead of the full bucket grid.
        Falls back to the full ``warmup`` when the manifest records
        nothing usable (absent, or config drifted)."""
        valid = set(_pow2_buckets(self.max_slots))
        b = (self.artifact_manifest or {}).get("buckets", {})
        batches = [n for n in b.get("batch", []) if n in valid]
        if not batches:
            return self.warmup(max_new_tokens=max_new_tokens)
        return sum(self.warmup(batch=n, max_new_tokens=max_new_tokens)
                   for n in batches)

    # ------------------------------------------------------------ metrics

    def warmup_metrics(self) -> Dict[str, Any]:
        """The warm-up grid's rounds (``_close_warmup_round``) and the
        totals of each part over them."""
        rounds = self._warmup_rounds
        totals = {k: sum(r[k] for r in rounds) for k in (
            "wall_s", "trace_s", "lower_s", "compile_s", "cache_retrieval_s",
            "run_s", "cache_hits", "cache_misses")}
        return {"rounds": list(rounds), **totals}

    def _compiles_after_warmup(self) -> Dict[str, Any]:
        """What the process traced, lowered and compiled since this
        engine's warm-up ended (its construction, for one never warmed):
        the compiler's counters since then, and the newest programs by
        name. The log is the process's: a second engine's programs, or a
        staging thread's, are in it too."""
        now = compile_cache.compile_counters()
        was = self._warm_counters
        entries: List[Dict[str, Any]] = []
        group: List[Dict[str, Any]] = []   # one program's records
        for r in compile_cache.compile_log(self._warm_log_index)[0]:
            group.append(r)
            if r["phase"] == "backend_compile":
                parts = compile_cache.log_summary(group)
                entries.append({
                    "program": r["fun_name"], "t0": r["t0"],
                    "trace_s": parts["trace_s"], "lower_s": parts["lower_s"],
                    "compile_s": r["dur_s"], "cache": r["cache"],
                    "span": r.get("span")})
                group = []
        return {
            "count": now["backend_compiles"] - was["backend_compiles"],
            "seconds": sum(now[k] - was[k] for k in (
                "trace_s", "lower_s", "backend_compile_s")),
            "last": entries[-32:]}

    def get_metrics(self) -> Dict[str, Any]:
        offload_m: Dict[str, Any] = {}
        if self._offload is not None:
            # hidden-latency ESTIMATE (not a measurement): prefill seconds
            # the host-tier hits avoided, priced at this engine's own mean
            # prefill rate — host_hit_tokens × (prefill wall / prompt
            # tokens prefilled). Honest as a ratio of work displaced; the
            # truly hidden share also depends on how much of the upload
            # overlapped batch formation.
            rate = (self.prefill_stats.total / self._total_prompt_tokens
                    if self._total_prompt_tokens else 0.0)
            offload_m = {
                "swap_outs": self._swap_outs,
                "swap_resumes": self._swap_resumes,
                "swap_fallback_finishes": self._swap_fallbacks,
                "swapped_parked": len(self._swapped),
                "prefetch_hidden_latency_est_s": (
                    self.kv._host_hit_tokens * rate),
            }
        groups: Dict[str, Dict[str, Any]] = {}
        for name, n in self._counters.items():
            group, key = name.split(".")
            groups.setdefault(group, {})[key] = n
        if "state" in groups:
            groups["state"]["step_body"] = self.state_step_body
        return {
            "total_requests": self._total_requests,
            "total_prompt_tokens": self._total_prompt_tokens,
            "total_generated_tokens": self._total_generated,
            "waiting": self.n_waiting,
            "live_slots": len(self._slots),
            # requests this engine runs at once (what a coordinator's pool
            # to the worker follows) and, of a per-layer spec, the residual
            # around its sublayers ("mhc": hyper-connection streams)
            "slots": self.max_slots,
            "residual": self.spec.residual,
            "admission_denied": self._admission_denied,
            "rejected_queue_full": self._rejected_full,
            "shed_deadline": self._shed_deadline,
            "deadline_expired": self._deadline_expired,
            "capacity_finishes": self._capacity_finishes,
            "engine_steps": self._steps,
            "prefill_calls": self._prefill_calls,
            "prefix_hit_admissions": self._prefix_hit_admissions,
            # 1 when a deploy asked for prefix reuse over a per-layer spec
            # (no prefill that continues from cached pages): off from the
            # spec, never a page hit
            "prefix_disabled_per_layer": self._prefix_disabled_per_layer,
            # decode steps the harvested chunks ran
            "decode_steps": self._decode_steps,
            "decode_chunks": self._decode_chunks,
            # sequences of a per-layer spec re-queued as prompt + tokens
            # when the pool ran dry
            "reprefill_preemptions": self._reprefill_preemptions,
            # the families' counters by group (see __init__; what each key
            # holds: ``docs/observability.md``): ``mla`` and ``moe`` of every
            # spec, ``attn`` where the paged layers keep K|V rows, ``state``
            # where some layers are recurrent
            **groups,
            "warmup": self.warmup_metrics(),
            "compiles_after_warmup": self._compiles_after_warmup(),
            "prefilling_slots": len(self._prefilling),
            "chunked_admissions": self._chunked_admissions,
            # does a freed slot find its successor here (see __init__)
            "admissions": self._admissions,
            "admissions_from_queue": self._admissions_from_queue,
            "empty_slot_dispatches": self._empty_slot_dispatches,
            # how often the one sequence engages (see __init__)
            "admissions_ahead": self._admissions_ahead,
            "finishes_learned_late": self._finishes_learned_late,
            "harvest_wait_s_total": self._harvest_wait_s,
            "sync_fallback_iterations": self._sync_fallback_iterations,
            # serving metrics the reference's mock could never know
            # (SURVEY.md §5): per-request TTFT from submit, and mean decode
            # batch occupancy (live slots / max_slots per engine step)
            # host-gap split (ISSUE 5): seconds inside dispatch brackets
            # vs host-side gaps between them, and the gap's share of the
            # measured wall — the at-a-glance attribution for hbm_util
            # regressions (kernel-side vs scheduler-side)
            "dispatch_s_total": self._dispatch_s,
            "host_gap_s_total": self._host_gap_s,
            "host_bubble_frac": (
                self._host_gap_s / (self._dispatch_s + self._host_gap_s)
                if (self._dispatch_s + self._host_gap_s) > 0 else 0.0),
            # chunks shortened because a live slot streams (ISSUE 13)
            "stream_clamped_chunks": self._stream_clamped_chunks,
            # decode chunks with a streamed slot, by where their tokens'
            # callbacks ran: under a chunk in flight, or with no program
            # to hide them (only finishing slots had any, an idle engine,
            # a sync fallback, abort, shutdown)
            "emit_carried_chunks": self._emit_carried_chunks,
            "emit_flushed_chunks": self._emit_flushed_chunks,
            "ttft": self.ttft_stats.snapshot(),
            # submit -> slot held and prefill dispatched
            "queue_wait": self.queue_wait_stats.snapshot(),
            "batch_occupancy": (self._occupancy_sum
                                / (self._steps * self.max_slots)
                                if self._steps else 0.0),
            "prefill": self.prefill_stats.snapshot(),
            "decode_chunk": self.chunk_stats.snapshot(),
            "kv": self.kv.get_stats(),
            **({"kv_offload": offload_m} if offload_m else {}),
            # the resolved attention path ("auto" resolved at init) and
            # the decode chunks dispatched on it: K/V read in place from
            # the page pool by a kernel, or through the dense copy
            "attn_impl": self.attn_impl,
            "decode_chunks_in_place": (
                self._decode_chunks if self.body == "window" else 0),
            "decode_chunks_dense": (
                self._decode_chunks if self.body == "dense" else 0),
        }
