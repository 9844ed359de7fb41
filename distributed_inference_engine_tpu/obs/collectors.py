"""Collector mappings: each component's ``get_stats()``/``get_metrics()``
dict → stable metric families in a ``MetricsRegistry``.

The mapping TABLES below are the single source of truth for the metric
catalog: ``CATALOG`` (name → kind, labels, help) is derived from them, the
docs table in ``docs/observability.md`` is linted against it (both
directions, ``scripts/lint_metrics.py``), and ``ensure_families()``
registers every family so an exposition always carries the full catalog's
``# TYPE``/``# HELP`` lines even for components that aren't live yet.

Apply functions are pure dict→registry transformations (no component
imports, no jax) so they are unit-testable on a bare interpreter and
usable from bench scripts against saved stats dicts.

Label conventions:
- per-engine families (``engine_*``, ``kv_*``, ``offload_*``, ``pump_*``)
  carry ``model`` and ``worker_id`` (empty ``worker_id`` for a local
  engine outside any worker);
- ``worker_*`` families carry ``worker_id``;
- coordinator-side singletons (``coordinator_*``, ``batcher_*``,
  ``cache_*``, ``router_*``, ``lb_*``, ``registry_*``) are unlabelled,
  except the per-worker and per-health breakdowns noted in the tables.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Tuple

from .registry import MetricsRegistry

MODEL_LABELS = ("model", "worker_id")
WORKER_LABELS = ("worker_id",)

# -- mapping tables --------------------------------------------------------
# (source_key, metric_name, kind, help); kind: c=counter g=gauge h=histogram

ENGINE_TABLE = [
    ("total_requests", "engine_requests", "c",
     "Requests accepted by the engine"),
    ("total_prompt_tokens", "engine_prompt_tokens", "c",
     "Prompt tokens prefetched/prefilled"),
    ("total_generated_tokens", "engine_generated_tokens", "c",
     "Tokens generated (post stop-trim)"),
    ("total_errors", "engine_errors", "c", "Engine-level request errors"),
    ("admission_denied", "engine_admission_denied", "c",
     "Admissions denied (no slot/pages at the time)"),
    ("rejected_queue_full", "engine_rejected_queue_full", "c",
     "Requests shed at submit: waiting queue full"),
    ("shed_deadline", "engine_shed_deadline", "c",
     "Requests shed after exceeding the queue deadline"),
    ("deadline_expired", "engine_deadline_expired", "c",
     "Requests expired in-queue by their own deadline_s budget"),
    ("capacity_finishes", "engine_capacity_finishes", "c",
     "Sequences force-finished (reason=length) by KV-pool exhaustion"),
    ("engine_steps", "engine_steps", "c",
     "Engine iterations that dispatched a decode chunk"),
    ("prefill_calls", "engine_prefill_calls", "c",
     "Prefill dispatches (whole-prompt or chunk)"),
    ("prefix_hit_admissions", "engine_prefix_hit_admissions", "c",
     "Admissions that reused cached prefix KV pages"),
    ("chunked_admissions", "engine_chunked_admissions", "c",
     "Admissions that prefill in chunks"),
    ("admissions", "engine_admissions", "c",
     "Requests given a slot"),
    ("admissions_from_queue", "engine_admissions_from_queue", "c",
     "Admissions whose request was already queued when its slot was freed"),
    ("empty_slot_dispatches", "engine_empty_slot_dispatches", "c",
     "Decode dispatches sent with a free slot and nothing queued"),
    ("admissions_ahead", "engine_admissions_ahead", "c",
     "Successors prefilled behind the chunk their predecessor ended in"),
    ("finishes_learned_late", "engine_finishes_learned_late", "c",
     "Finishes only a read could tell (EOS, stop, grant): one chunk of one slot each"),
    ("harvest_wait_s_total", "engine_harvest_wait_seconds", "c",
     "Seconds blocked reading decode chunks' packed outputs"),
    ("sync_fallback_iterations", "engine_sync_fallback_iterations", "c",
     "Iterations that read the chunk in flight before dispatching (pool not backed a chunk ahead)"),
    ("rounds", "engine_spec_rounds", "c",
     "Speculative target+draft verification rounds"),
    ("waiting", "engine_waiting", "g", "Requests in the waiting queue"),
    ("live_slots", "engine_live_slots", "g", "Decoding slots right now"),
    ("prefilling_slots", "engine_prefilling_slots", "g",
     "Slots mid chunked prefill"),
    ("batch_occupancy", "engine_batch_occupancy", "g",
     "Mean live slots / max_slots per engine step"),
    ("dispatch_s_total", "engine_dispatch_seconds", "c",
     "Seconds inside device dispatch brackets (host-gap split)"),
    ("host_gap_s_total", "engine_host_gap_seconds", "c",
     "Host seconds from the end of one blocking read to the next dispatch"),
    ("host_bubble_frac", "engine_host_bubble_fraction", "g",
     "Host gap share of dispatch+gap wall (roofline split)"),
    ("speculate_k", "engine_spec_k", "g", "Draft tokens proposed per round"),
    ("draft_acceptance_rate", "engine_spec_draft_acceptance_rate", "g",
     "Accepted / proposed draft tokens"),
    ("tokens_per_round", "engine_spec_tokens_per_round", "g",
     "Mean tokens emitted per speculative round"),
    ("stream_clamped_chunks", "engine_stream_clamped_chunks", "c",
     "Decode chunks shortened by the adaptive streaming clamp"),
    ("emit_carried_chunks", "engine_emit_carried_chunks", "c",
     "Decode chunks whose tokens were streamed under a chunk in flight"),
    ("emit_flushed_chunks", "engine_emit_flushed_chunks", "c",
     "Decode chunks whose tokens were streamed with no program to hide it"),
    ("ttft", "engine_ttft_seconds", "h",
     "Time to first token (continuous: from submit, incl. queue wait)"),
    ("queue_wait", "engine_queue_wait_seconds", "h",
     "Submit to admitted: the wait for a slot, until the prefill is dispatched"),
    ("prefill", "engine_prefill_seconds", "h",
     "Prefill dispatched to its first tokens on the host"),
    ("decode_chunk", "engine_decode_chunk_seconds", "h",
     "Blocking residue of a decode chunk's packed read"),
    ("decode", "engine_decode_seconds", "h",
     "Decode wall time per generate call (static/speculative engines)"),
]

ENGINE_OFFLOAD_TABLE = [          # engine.get_metrics()["kv_offload"]
    ("swap_outs", "engine_swap_outs", "c",
     "Decode victims swapped to the host tier under pool pressure"),
    ("swap_resumes", "engine_swap_resumes", "c",
     "Swapped sequences resumed with no re-prefill"),
    ("swap_fallback_finishes", "engine_swap_fallback_finishes", "c",
     "Swap attempts the host tier refused (finished reason=length)"),
    ("swapped_parked", "engine_swapped_parked", "g",
     "Sequences currently parked on the host tier"),
    ("prefetch_hidden_latency_est_s",
     "engine_prefetch_hidden_latency_est_seconds", "g",
     "Estimated prefill seconds displaced by host-tier prefix hits"),
]

ENGINE_MLA_TABLE = [              # engine.get_metrics()["mla"]
    ("decode_context_rows", "engine_mla_decode_context_rows", "c",
     "Latent rows the decode steps attended to, per paged layer (per-layer specs)"),
    ("decode_table_rows", "engine_mla_decode_table_rows", "c",
     "Latent rows the decode body read for them, counted in the program: "
     "the kernel's live pages (the XLA body: a layer's whole table), every "
     "step"),
    ("prefill_key_blocks_visited", "engine_mla_prefill_key_blocks_visited", "c",
     "Key blocks the admitted prompts' latent prefills visited, per paged layer"),
    ("prefill_key_blocks_bucket", "engine_mla_prefill_key_blocks_square", "c",
     "Key blocks of their buckets' whole squares: what no skipping would visit"),
]

ENGINE_ATTN_TABLE = [             # engine.get_metrics()["attn"]
    ("full_prefill_key_blocks_visited",
     "engine_attn_full_prefill_key_blocks_visited", "c",
     "Key blocks the admitted prompts' full-attention prefills visited, per "
     "full layer (specs whose paged layers keep K|V rows)"),
    ("full_prefill_key_blocks_bucket",
     "engine_attn_full_prefill_key_blocks_square", "c",
     "Key blocks of their buckets' whole squares: what no skipping would visit"),
    ("window_prefill_key_blocks_visited",
     "engine_attn_window_prefill_key_blocks_visited", "c",
     "Key blocks inside the band the sliding-window prefills visited, per "
     "sliding layer"),
    ("window_prefill_key_blocks_bucket",
     "engine_attn_window_prefill_key_blocks_square", "c",
     "Key blocks of their buckets' whole squares"),
]

ENGINE_WARMUP_TABLE = [           # engine.get_metrics()["warmup"]
    ("run_s", "worker_warmup_run_seconds", "g",
     "Warm-up seconds outside trace, lower and compile: programs running"),
]

ENGINE_AFTER_WARMUP_TABLE = [ # get_metrics()["compiles_after_warmup"]
    ("count", "worker_compiles_after_warmup", "c",
     "Programs the backend was asked for since the engine's warm-up ended"),
]

KV_TABLE = [                       # PagedKVCache.get_stats()
    ("num_pages", "kv_pages", "g", "HBM page-pool size"),
    ("page_size", "kv_page_size", "g", "Tokens per KV page"),
    ("pages_used", "kv_pages_used", "g", "Pages allocated to live slots"),
    ("pages_free", "kv_pages_free", "g", "Pages on the free list"),
    ("pages_cached", "kv_pages_cached", "g",
     "Reclaimable pages held by the prefix cache"),
    ("peak_pages_used", "kv_peak_pages_used", "g",
     "High-water pages_used since start"),
    ("utilization", "kv_utilization", "g", "pages_used / num_pages"),
    ("live_slots", "kv_live_slots", "g", "Slots with page tables"),
    ("free_slots", "kv_free_slots", "g", "Unassigned slot ids"),
    ("prefix_queries", "kv_prefix_queries", "c",
     "Prefix-cache lookups at admission"),
    ("prefix_hit_pages", "kv_prefix_hit_pages", "c",
     "Pages served from the prefix cache"),
    ("prefix_hit_tokens", "kv_prefix_hit_tokens", "c",
     "Prompt tokens whose prefill was skipped via prefix hits"),
    ("prefix_reclaimed", "kv_prefix_reclaimed", "c",
     "Cached pages reclaimed for new allocations"),
    ("prefix_indexed", "kv_prefix_indexed", "g",
     "Page hashes currently in the prefix index"),
    ("hbm_bytes", "kv_hbm_bytes", "g", "Device bytes held by the page pools"),
]

OFFLOAD_TABLE = [                  # kv get_stats()["host_tier"]
    ("host_max_bytes", "offload_host_max_bytes", "g",
     "Host-tier byte budget"),
    ("host_lru_bytes", "offload_host_lru_bytes", "g",
     "Host bytes held by the LRU store"),
    ("host_swap_bytes", "offload_host_swap_bytes", "g",
     "Host bytes reserved by swapped decode state"),
    ("host_pages", "offload_host_pages", "g", "Pages resident on host"),
    ("offloaded_pages", "offload_offloaded_pages", "c",
     "Pages copied device to host on eviction"),
    ("offloaded_bytes", "offload_offloaded_bytes", "c",
     "Bytes copied device to host on eviction"),
    ("host_hit_pages", "offload_hit_pages", "c",
     "Host-tier pages matched by prefix probes"),
    ("host_hit_bytes", "offload_hit_bytes", "c",
     "Host-tier bytes matched by prefix probes"),
    ("host_staged_pages", "offload_staged_pages", "c",
     "Pages staged for host to device upload"),
    ("host_evicted_pages", "offload_evicted_pages", "c",
     "Host-tier pages evicted by the byte budget"),
    ("host_rejected_pages", "offload_rejected_pages", "c",
     "Offload attempts refused by the byte budget"),
    ("host_hit_pages_admit", "offload_hit_pages_admit", "c",
     "Host-tier pages actually restaged at admission"),
    ("host_hit_tokens", "offload_hit_tokens", "c",
     "Prompt tokens restaged from the host tier"),
    ("uploaded_pages", "offload_uploaded_pages", "c",
     "Pages uploaded host to device"),
    ("uploaded_bytes", "offload_uploaded_bytes", "c",
     "Bytes uploaded host to device"),
    ("pending_offload", "offload_pending_offload", "g",
     "Device to host copies queued for the next sync"),
    ("pending_upload", "offload_pending_upload", "g",
     "Host to device uploads in flight"),
    ("restage_overlap_s", "kv_fabric_restage_overlap_seconds", "c",
     "Seconds host-to-device restaging ran overlapped (staged layer-wise "
     "at prefetch, consumed at admission)"),
]

PUMP_TABLE = [                     # EnginePump.get_stats() (sans "engine")
    ("in_flight", "pump_in_flight", "g",
     "Requests inside the pump (inbox + engine)"),
    ("thread_alive", "pump_thread_alive", "g",
     "1 while the engine thread is running"),
    ("steps", "pump_steps", "c", "engine.step() calls by the pump thread"),
    ("step_errors", "pump_step_errors", "c",
     "Engine steps that raised (backed off and continued)"),
    ("inbox_depth", "pump_inbox_depth", "g",
     "Requests enqueued but not yet admitted"),
    ("inbox_wait", "pump_inbox_wait_seconds", "h",
     "Enqueue by an RPC handler to engine.submit() on the pump thread"),
]

BATCHER_TABLE = [                  # Batcher.get_stats()
    ("running", "batcher_running", "g", "1 while the batcher loop runs"),
    ("total_requests", "batcher_requests", "c", "Requests enqueued"),
    ("total_batches", "batcher_batches", "c", "Batches dispatched"),
    ("total_batched_requests", "batcher_batched_requests", "c",
     "Requests dispatched inside batches"),
    ("total_errors", "batcher_errors", "c", "Batch dispatch errors"),
    ("avg_batch_size", "batcher_avg_batch_size", "g",
     "Mean requests per dispatched batch"),
    ("pending_batches", "batcher_pending_batches", "g",
     "Batches still collecting requests"),
    ("pending_requests", "batcher_pending_requests", "g",
     "Requests waiting in pending batches"),
    ("inflight_batches", "batcher_inflight_batches", "g",
     "Batches dispatched and awaiting results"),
    ("queue_wait", "batcher_queue_wait_seconds", "h",
     "Enqueue to batch-dispatch wait"),
]

CACHE_TABLE = [                    # ResponseCache.get_stats()
    ("size", "cache_size", "g", "Entries in the response cache"),
    ("max_size", "cache_max_size", "g", "Response-cache capacity"),
    ("hits", "cache_hits", "c", "Response-cache hits"),
    ("misses", "cache_misses", "c", "Response-cache misses"),
    ("hit_rate", "cache_hit_rate", "g", "hits / (hits + misses)"),
    ("evictions", "cache_evictions", "c", "Entries evicted by capacity"),
    ("expirations", "cache_expirations", "c", "Entries expired by TTL"),
]

ROUTER_TABLE = [                   # ShardRouter.get_stats()
    ("workers", "router_workers", "g", "Workers known to the router"),
    ("route_count", "router_routes", "c", "Routing decisions"),
    ("failover_count", "router_failovers", "c",
     "Routes diverted off an unhealthy worker"),
    ("routing_errors", "router_errors", "c", "Routing failures"),
]

LB_TABLE = [                       # LoadBalancer.get_all_stats()
    ("pick_count", "lb_picks", "c", "Load-balancer worker picks"),
    ("healthy_count", "lb_healthy_workers", "g", "Healthy workers"),
    ("affinity_hits", "lb_affinity_hits", "c",
     "Prefix-affinity picks that landed on the bound (warm) worker"),
    ("affinity_misses", "lb_affinity_misses", "c",
     "Prefix-affinity picks with no live binding (cold prefix)"),
    ("affinity_rebinds", "lb_affinity_rebinds", "c",
     "Affinity bindings dropped or moved off a dead/drained worker"),
    ("affinity_bindings", "lb_affinity_bindings", "g",
     "Live prefix-to-worker affinity bindings"),
]

LB_WORKER_TABLE = [                # get_all_stats()["workers"][wid]
    ("request_count", "lb_worker_requests", "c",
     "Requests dispatched to this worker"),
    ("error_count", "lb_worker_errors", "c", "Dispatch failures"),
    ("active_connections", "lb_worker_active_connections", "g",
     "In-flight dispatches held by the LB"),
    ("avg_latency_s", "lb_worker_avg_latency_seconds", "g",
     "Mean dispatch latency"),
    ("healthy", "lb_worker_healthy", "g", "1 if the LB considers it healthy"),
    ("breaker_state_code", "lb_worker_breaker_state", "g",
     "Circuit breaker state: 0 closed, 1 half-open, 2 open"),
    ("breaker_opens", "lb_worker_breaker_opens", "c",
     "Times this worker's circuit breaker opened"),
]

REGISTRY_TABLE = [                 # ModelRegistry.get_stats()
    ("models", "registry_models", "g", "Distinct models registered"),
    ("versions", "registry_versions", "g", "Model versions registered"),
    ("shards", "registry_shards", "g", "Shard placements registered"),
    ("workers", "registry_workers", "g", "Workers serving any model"),
]

COORDINATOR_TABLE = [              # Coordinator.get_stats() top level
    ("submitted", "coordinator_submitted", "c",
     "Requests submitted to the coordinator"),
    ("cache_hits", "coordinator_cache_hits", "c",
     "Submissions answered from the response cache"),
    ("overload_rejections", "coordinator_overload_rejections", "c",
     "Submissions shed by every tried replica"),
    ("dispatch_retries", "coordinator_dispatch_retries", "c",
     "Re-dispatches after transport failures or draining sheds"),
    ("stream_resumes", "coordinator_stream_resumes", "c",
     "Streams resumed on an alternate worker via prefix replay"),
    ("stream_frames", "coordinator_stream_frames", "c",
     "Streamed token frames relayed to consumers"),
    ("stream_itl", "coordinator_stream_itl_seconds", "h",
     "Inter-frame gap at stream delivery (resets across failover)"),
    ("streams_in_flight", "coordinator_streams_in_flight", "g",
     "Stream dispatches holding or waiting for a worker connection"),
    ("pool_waiting", "coordinator_pool_waiting", "g",
     "Stream dispatches waiting for a pooled worker connection"),
    ("pool_in_use", "coordinator_pool_in_use", "g",
     "Worker connections held by calls in flight"),
    ("pool_size", "coordinator_pool_size", "g",
     "Worker connections the pools may hold (follows the workers' slots)"),
    ("pool_wait", "coordinator_pool_wait_seconds", "h",
     "Wait of a stream dispatch for a pooled worker connection"),
    ("deadline_expired", "coordinator_deadline_expired", "c",
     "Requests answered with the typed deadline outcome"),
    ("drains", "coordinator_drains", "c",
     "Graceful worker drains completed"),
    ("supervisor_respawns", "supervisor_respawns", "c",
     "Unhealthy workers respawned and re-admitted by the supervisor"),
    ("supervisor_crashloop_opens", "supervisor_crashloop_opens", "c",
     "Crash-loop breakers opened (worker given up on, shards FAILED)"),
    ("admission_sheds", "coordinator_admission_sheds", "c",
     "Requests shed at coordinator admission (fleet-level degradation)"),
    ("admission_shed_active", "coordinator_admission_shed_active", "g",
     "1 while fleet-level admission shedding is engaged"),
    ("kv_fabric_prewarm_pushes", "kv_fabric_prewarm_pushes", "c",
     "Prefix wires pushed into workers before half-open rejoin"),
]

AUTOSCALER_TABLE = [               # FleetAutoscaler.get_stats()
    ("fleet_size", "autoscaler_fleet_size", "g",
     "Workers currently governed by the autoscaler"),
    ("slo_attainment", "autoscaler_slo_attainment", "g",
     "Latest SLO attainment (1.0 = every target met)"),
    ("ticks", "autoscaler_ticks", "c", "Policy evaluations run"),
    ("scale_ups", "autoscaler_scale_ups", "c",
     "Scale-up actions (spawn + half-open rejoin)"),
    ("scale_downs", "autoscaler_scale_downs", "c",
     "Scale-down actions (graceful drain + remove)"),
    ("guard_holds", "autoscaler_guard_holds", "c",
     "Ticks held by the breaker/supervisor guard"),
]

UPGRADE_TABLE = [                  # RollingUpgrade.get_stats()
    ("upgraded", "upgrade_workers", "c",
     "Workers upgraded (drain, artifact swap, probe, half-open rejoin)"),
    ("probe_failures", "upgrade_probe_failures", "c",
     "Golden probes failed by a swapped-in worker"),
    ("rollbacks", "upgrade_rollbacks", "c",
     "Upgrades rolled back to the prior artifact after a failed probe"),
    ("in_progress", "upgrade_in_progress", "g",
     "1 while a rolling upgrade is running"),
]

WORKER_TABLE = [                   # WorkerServer.get_metrics() top level
    ("uptime_s", "worker_uptime_seconds", "g", "Seconds since start"),
    ("request_count", "worker_requests", "c",
     "generate/generate_stream RPCs served"),
    ("error_count", "worker_errors", "c", "RPC handler errors"),
    ("overloaded_count", "worker_overloaded", "c",
     "Requests shed by engine overload handling"),
    ("deadline_expired_count", "worker_deadline_expired", "c",
     "Requests whose deadline_s budget expired on this worker"),
    ("draining", "worker_draining", "g",
     "1 while the worker refuses admission (drain in progress)"),
    ("drain_count", "worker_drains", "c", "Drain RPCs honored"),
    ("injected_faults", "worker_injected_faults", "c",
     "Chaos faults injected into this worker's server plane"),
    ("handoff_bytes_shipped", "worker_handoff_bytes_shipped", "c",
     "Disaggregated KV handoff bytes sent to decode peers"),
    ("kv_fabric_exports", "kv_fabric_exports", "c",
     "kv_export RPCs that produced a prefix wire"),
    ("kv_fabric_imports", "kv_fabric_imports", "c",
     "kv_import RPCs that landed pages in the host KV tier"),
    ("kv_fabric_export_bytes", "kv_fabric_export_bytes", "c",
     "KV page payload bytes exported over the fabric"),
    ("kv_fabric_import_bytes", "kv_fabric_import_bytes", "c",
     "KV page payload bytes imported over the fabric"),
    ("kv_fabric_import_fallbacks", "kv_fabric_import_fallbacks", "c",
     "Imports rejected (checksum/shape) — worker falls back to prefill"),
    ("ping_count", "worker_pings", "c", "Health probes answered"),
    ("active_connections", "worker_active_connections", "g",
     "Open RPC connections"),
    ("artifact_hits", "worker_artifact_hits", "c",
     "Model loads cold-started from a pre-fused serving artifact"),
    ("artifact_misses", "worker_artifact_misses", "c",
     "Artifact-configured loads that fell back to the slow path"),
    ("latency", "worker_request_seconds", "h",
     "generate/generate_stream RPC wall time"),
    ("model_load", "worker_model_load_seconds", "h",
     "load_model wall time (artifact cold-start vs slow path)"),
    ("resident_models", "worker_resident_models", "g",
     "Models resident (engine built, serving-ready) on this worker"),
    ("resident_bytes", "worker_resident_bytes", "g",
     "Parameter bytes held by resident models"),
    ("staged_models", "worker_staged_models", "g",
     "Models staging in the background (built, not yet swapped in)"),
    ("stage_started", "worker_stage_started", "c",
     "Background model stages started"),
    ("stage_completed", "worker_stage_completed", "c",
     "Background model stages that finished building"),
    ("stage_failed", "worker_stage_failed", "c",
     "Background model stages that raised during build"),
    ("model_swaps", "worker_model_swaps", "c",
     "Hot swaps that activated a staged model"),
    ("model_evictions", "worker_model_evictions", "c",
     "Idle models evicted by the resident count/byte budget (LRU)"),
    ("swap_probe_rejects", "worker_swap_probe_rejects", "c",
     "Swaps refused by the golden-token probe (staged engine discarded)"),
    ("stage_overlap_steps", "worker_stage_overlap_steps", "c",
     "Engine steps served by resident models while a stage ran"),
    ("model_stage", "worker_stage_seconds", "h",
     "Background stage wall time (artifact restore off the dispatch path)"),
    ("model_swap", "worker_model_swap_seconds", "h",
     "swap_model wall time the caller observed (stage overlap excluded)"),
]

# families whose label values are dynamic (declared here so the catalog
# and ensure_families still cover them)
EXTRA_FAMILIES = [
    ("router_workers_by_health", "g", ("health",),
     "Workers per router health state"),
    ("router_worker_routes", "c", ("worker_id",),
     "Routing decisions landing on this worker"),
    ("worker_rss_bytes", "g", WORKER_LABELS,
     "Worker process resident set size (psutil, 0 if unavailable)"),
    ("fleet_worker_role", "g", ("worker_id", "role"),
     "1 for the worker's fleet role: prefill / decode / replica"),
    ("coordinator_stream_emit_lag_seconds", "g", ("worker_id",),
     "Last inter-frame gap observed per worker on streamed frames"),
    ("autoscaler_decisions", "c", ("action",),
     "Scaling decisions by action: up / down / shed_on / shed_off"),
    ("lb_model_affinity_hits", "c", ("model",),
     "Model+prefix affinity picks that landed on the bound worker"),
    ("lb_model_affinity_misses", "c", ("model",),
     "Model+prefix affinity picks with no live binding (cold key)"),
    ("lb_model_affinity_rebinds", "c", ("model",),
     "Model+prefix bindings moved off a dead/drained worker"),
    ("obs_scrape_seconds", "h", ("server",),
     "Wall time to collect and render one /metrics exposition"),
    ("obs_scrape_ok", "g", ("server",),
     "1 if the last /metrics scrape rendered without error"),
    ("obs_events_emitted", "c", ("proc",),
     "Typed fleet events emitted into this process's ring"),
    ("obs_events_dropped", "c", ("proc",),
     "Fleet events overwritten by ring wrap (oldest evicted)"),
    ("slo_ticks", "c", (),
     "SLO burn-rate engine evaluation ticks"),
    ("slo_burn_rate_fast", "g", ("objective",),
     "Fast-window error-budget burn rate (1.0 = budget-neutral)"),
    ("slo_burn_rate_slow", "g", ("objective",),
     "Slow-window error-budget burn rate (1.0 = budget-neutral)"),
    ("slo_breach_active", "g", ("objective",),
     "1 while this objective's multi-window burn breach is engaged"),
    ("slo_breach_transitions", "c", ("objective",),
     "Burn-breach on/off transitions for this objective"),
]

WORKER_COMPILE_TABLE = [           # get_metrics()["device"]["compile"]
    ("backend_compiles", "worker_backend_compiles", "c",
     "Programs the backend was asked for (XLA compiles and compile-cache loads)"),
    ("backend_compile_s", "worker_backend_compile_seconds", "c",
     "Seconds spent in those backend compile requests"),
    ("cache_hits", "worker_compile_cache_hits", "c",
     "Programs supplied by the persistent compile cache"),
    ("cache_misses", "worker_compile_cache_misses", "c",
     "Programs XLA compiled and the persistent cache then stored"),
    ("trace_s", "worker_backend_trace_seconds", "c",
     "Seconds tracing Python functions to jaxprs (inner jits not twice)"),
    ("lower_s", "worker_backend_lower_seconds", "c",
     "Seconds lowering jaxprs to MLIR modules (Mosaic kernels included)"),
    ("cache_retrieval_s", "worker_compile_cache_retrieval_seconds", "c",
     "Seconds of the backend compile requests spent reading cache entries"),
]

_GROUPS: List[Tuple[List, Tuple[str, ...]]] = [
    (ENGINE_TABLE, MODEL_LABELS),
    (ENGINE_OFFLOAD_TABLE, MODEL_LABELS),
    (ENGINE_MLA_TABLE, MODEL_LABELS),
    (ENGINE_ATTN_TABLE, MODEL_LABELS),
    (ENGINE_WARMUP_TABLE, MODEL_LABELS),
    (ENGINE_AFTER_WARMUP_TABLE, MODEL_LABELS),
    (KV_TABLE, MODEL_LABELS),
    (OFFLOAD_TABLE, MODEL_LABELS),
    (PUMP_TABLE, MODEL_LABELS),
    (BATCHER_TABLE, ()),
    (CACHE_TABLE, ()),
    (ROUTER_TABLE, ()),
    (LB_TABLE, ()),
    (LB_WORKER_TABLE, WORKER_LABELS),
    (REGISTRY_TABLE, ()),
    (COORDINATOR_TABLE, ()),
    (WORKER_TABLE, WORKER_LABELS),
    (WORKER_COMPILE_TABLE, WORKER_LABELS),
    (AUTOSCALER_TABLE, ()),
    (UPGRADE_TABLE, ()),
]

_KINDS = {"c": "counter", "g": "gauge", "h": "histogram"}


def _build_catalog() -> Dict[str, Tuple[str, Tuple[str, ...], str]]:
    cat: Dict[str, Tuple[str, Tuple[str, ...], str]] = {}
    for table, labels in _GROUPS:
        for _src, name, kind, help in table:
            prev = cat.get(name)
            entry = (_KINDS[kind], labels, help)
            if prev is not None and prev[:2] != entry[:2]:
                raise AssertionError(f"catalog conflict for {name}")
            cat[name] = entry
    for name, kind, labels, help in EXTRA_FAMILIES:
        cat[name] = (_KINDS[kind], tuple(labels), help)
    return cat


#: metric family name -> (kind, labelnames, help). The docs catalog table
#: is linted against exactly this mapping (scripts/lint_metrics.py).
CATALOG: Dict[str, Tuple[str, Tuple[str, ...], str]] = _build_catalog()


def ensure_families(reg: MetricsRegistry) -> None:
    """Register every catalog family (idempotent) so the exposition always
    carries the full set of TYPE/HELP lines."""
    for name, (kind, labels, help) in CATALOG.items():
        getattr(reg, kind)(name, help, labels)


def clear_worker_labelled(reg: MetricsRegistry) -> None:
    """Drop children of every family labelled by worker_id so a rebuild
    collector doesn't leave series for departed workers behind."""
    for name in reg.names:
        fam = reg.get(name)
        if fam is not None and "worker_id" in fam.labelnames:
            fam.clear()


# -- apply functions -------------------------------------------------------

def _apply_table(reg: MetricsRegistry, table, src: Mapping[str, Any],
                 labelnames: Tuple[str, ...],
                 labels: Dict[str, str]) -> None:
    for src_key, name, kind, help in table:
        if src_key not in src:
            continue                       # subset-tolerant: engines differ
        v = src[src_key]
        if kind == "c":
            reg.counter(name, help, labelnames).labels(**labels).set(
                float(v))
        elif kind == "g":
            reg.gauge(name, help, labelnames).labels(**labels).set(float(v))
        elif kind == "h" and isinstance(v, Mapping):
            buckets = v.get("buckets")
            if buckets:
                reg.histogram(name, help, labelnames).labels(
                    **labels).set_snapshot(
                        buckets, v.get("sum_s", 0.0), v.get("count", 0))


def apply_engine(reg: MetricsRegistry, m: Optional[Mapping[str, Any]],
                 model: str = "", worker_id: str = "") -> None:
    """One engine's ``get_metrics()`` dict (continuous / static / fake /
    speculative — subset-tolerant), including its kv / host-tier /
    offload sub-dicts."""
    if not m:
        return
    labels = {"model": model, "worker_id": worker_id}
    _apply_table(reg, ENGINE_TABLE, m, MODEL_LABELS, labels)
    off = m.get("kv_offload")
    if isinstance(off, Mapping):
        _apply_table(reg, ENGINE_OFFLOAD_TABLE, off, MODEL_LABELS, labels)
    for key, table in (("mla", ENGINE_MLA_TABLE),
                       ("attn", ENGINE_ATTN_TABLE),
                       ("warmup", ENGINE_WARMUP_TABLE),
                       ("compiles_after_warmup", ENGINE_AFTER_WARMUP_TABLE)):
        if isinstance(m.get(key), Mapping):
            _apply_table(reg, table, m[key], MODEL_LABELS, labels)
    kv = m.get("kv")
    if isinstance(kv, Mapping):
        _apply_table(reg, KV_TABLE, kv, MODEL_LABELS, labels)
        host = kv.get("host_tier")
        if isinstance(host, Mapping):
            _apply_table(reg, OFFLOAD_TABLE, host, MODEL_LABELS, labels)


def apply_pump(reg: MetricsRegistry, ps: Optional[Mapping[str, Any]],
               model: str = "", worker_id: str = "") -> None:
    if not ps:
        return
    _apply_table(reg, PUMP_TABLE, ps, MODEL_LABELS,
                 {"model": model, "worker_id": worker_id})


def apply_batcher(reg: MetricsRegistry,
                  bs: Optional[Mapping[str, Any]]) -> None:
    if bs:
        _apply_table(reg, BATCHER_TABLE, bs, (), {})


def apply_cache(reg: MetricsRegistry,
                cs: Optional[Mapping[str, Any]]) -> None:
    if cs:
        _apply_table(reg, CACHE_TABLE, cs, (), {})


def apply_router(reg: MetricsRegistry,
                 rs: Optional[Mapping[str, Any]]) -> None:
    if not rs:
        return
    _apply_table(reg, ROUTER_TABLE, rs, (), {})
    by_health = rs.get("workers_by_health")
    if isinstance(by_health, Mapping):
        fam = reg.gauge("router_workers_by_health",
                        CATALOG["router_workers_by_health"][2], ("health",))
        for health, n in by_health.items():
            fam.labels(health=str(health)).set(float(n))
    detail = rs.get("worker_detail")
    if isinstance(detail, Mapping):
        fam = reg.counter("router_worker_routes",
                          CATALOG["router_worker_routes"][2], ("worker_id",))
        for wid, d in detail.items():
            if isinstance(d, Mapping) and "routes" in d:
                fam.labels(worker_id=str(wid)).set(float(d["routes"]))


def apply_lb(reg: MetricsRegistry, ls: Optional[Mapping[str, Any]]) -> None:
    if not ls:
        return
    _apply_table(reg, LB_TABLE, ls, (), {})
    by_model = ls.get("affinity_models")
    if isinstance(by_model, Mapping):
        fams = {f: reg.counter(f"lb_model_affinity_{f}",
                               CATALOG[f"lb_model_affinity_{f}"][2],
                               ("model",))
                for f in ("hits", "misses", "rebinds")}
        for model, rec in by_model.items():
            if isinstance(rec, Mapping):
                for f, fam in fams.items():
                    fam.labels(model=str(model)).set(float(rec.get(f, 0)))
    workers = ls.get("workers")
    if isinstance(workers, Mapping):
        for wid, ws in workers.items():
            if isinstance(ws, Mapping):
                _apply_table(reg, LB_WORKER_TABLE, ws, WORKER_LABELS,
                             {"worker_id": str(wid)})


def apply_registry_stats(reg: MetricsRegistry,
                         gs: Optional[Mapping[str, Any]]) -> None:
    if gs:
        _apply_table(reg, REGISTRY_TABLE, gs, (), {})


def apply_coordinator(reg: MetricsRegistry,
                      cs: Optional[Mapping[str, Any]]) -> None:
    """A ``Coordinator.get_stats()`` dict: top-level counters plus the
    cache / batcher / router / lb / registry sub-dicts."""
    if not cs:
        return
    _apply_table(reg, COORDINATOR_TABLE, cs, (), {})
    apply_cache(reg, cs.get("cache"))
    apply_batcher(reg, cs.get("batcher"))
    apply_router(reg, cs.get("router"))
    apply_lb(reg, cs.get("load_balancer"))
    apply_registry_stats(reg, cs.get("registry"))
    roles = cs.get("worker_roles")
    if isinstance(roles, Mapping):
        fam = reg.gauge("fleet_worker_role",
                        CATALOG["fleet_worker_role"][2],
                        ("worker_id", "role"))
        for wid, role in roles.items():
            fam.labels(worker_id=str(wid), role=str(role)).set(1.0)
    lag = cs.get("stream_emit_lag")
    if isinstance(lag, Mapping):
        fam = reg.gauge("coordinator_stream_emit_lag_seconds",
                        CATALOG["coordinator_stream_emit_lag_seconds"][2],
                        ("worker_id",))
        for wid, gap in lag.items():
            fam.labels(worker_id=str(wid)).set(float(gap))


def apply_autoscaler(reg: MetricsRegistry,
                     s: Optional[Mapping[str, Any]]) -> None:
    """A ``FleetAutoscaler.get_stats()`` dict: policy gauges/counters plus
    the per-action decision breakdown."""
    if not s:
        return
    _apply_table(reg, AUTOSCALER_TABLE, s, (), {})
    by_action = s.get("decisions_by_action")
    if isinstance(by_action, Mapping):
        fam = reg.counter("autoscaler_decisions",
                          CATALOG["autoscaler_decisions"][2], ("action",))
        for action, n in by_action.items():
            fam.labels(action=str(action)).set(float(n))


def apply_upgrade(reg: MetricsRegistry,
                  s: Optional[Mapping[str, Any]]) -> None:
    """A ``RollingUpgrade.get_stats()`` dict."""
    if s:
        _apply_table(reg, UPGRADE_TABLE, s, (), {})


def apply_slo(reg: MetricsRegistry, s: Optional[Mapping[str, Any]]) -> None:
    """A ``BurnRateEngine.get_stats()`` dict: tick counter plus the
    per-objective burn gauges and transition counters."""
    if not s:
        return
    if "ticks" in s:
        reg.counter("slo_ticks", CATALOG["slo_ticks"][2]).labels().set(
            float(s["ticks"]))
    objectives = s.get("objectives")
    if not isinstance(objectives, Mapping):
        return
    fams = {
        "burn_fast": reg.gauge("slo_burn_rate_fast",
                               CATALOG["slo_burn_rate_fast"][2],
                               ("objective",)),
        "burn_slow": reg.gauge("slo_burn_rate_slow",
                               CATALOG["slo_burn_rate_slow"][2],
                               ("objective",)),
        "breach_active": reg.gauge("slo_breach_active",
                                   CATALOG["slo_breach_active"][2],
                                   ("objective",)),
        "transitions": reg.counter("slo_breach_transitions",
                                   CATALOG["slo_breach_transitions"][2],
                                   ("objective",)),
    }
    for name, rec in objectives.items():
        if isinstance(rec, Mapping):
            for key, fam in fams.items():
                if key in rec:
                    fam.labels(objective=str(name)).set(float(rec[key]))


def apply_event_log(reg: MetricsRegistry, s: Optional[Mapping[str, Any]],
                    proc: str) -> None:
    """An ``EventLog.get_stats()`` dict for one process's ring."""
    if not s:
        return
    labels = {"proc": str(proc)}
    reg.counter("obs_events_emitted", CATALOG["obs_events_emitted"][2],
                ("proc",)).labels(**labels).set(
                    float(s.get("events_emitted", 0)))
    reg.counter("obs_events_dropped", CATALOG["obs_events_dropped"][2],
                ("proc",)).labels(**labels).set(
                    float(s.get("events_dropped", 0)))


def record_scrape(reg: MetricsRegistry, server: str, seconds: float,
                  ok: bool) -> None:
    """Self-observation for the /metrics plane: one scrape's collect+
    render wall time and outcome, recorded AFTER rendering so it shows
    up on the NEXT exposition (a scrape cannot time itself into its own
    output)."""
    labels = {"server": str(server)}
    reg.histogram("obs_scrape_seconds", CATALOG["obs_scrape_seconds"][2],
                  ("server",)).labels(**labels).observe(float(seconds))
    reg.gauge("obs_scrape_ok", CATALOG["obs_scrape_ok"][2],
              ("server",)).labels(**labels).set(1.0 if ok else 0.0)


def apply_worker(reg: MetricsRegistry, wm: Optional[Mapping[str, Any]],
                 worker_id: Optional[str] = None) -> None:
    """A ``WorkerServer.get_metrics()`` dict: worker families plus every
    loaded model's engine metrics and pump stats."""
    if not wm:
        return
    wid = str(worker_id if worker_id is not None
              else wm.get("worker_id", ""))
    _apply_table(reg, WORKER_TABLE, wm, WORKER_LABELS, {"worker_id": wid})
    proc = wm.get("process")
    if isinstance(proc, Mapping) and "rss_bytes" in proc:
        reg.gauge("worker_rss_bytes", CATALOG["worker_rss_bytes"][2],
                  WORKER_LABELS).labels(worker_id=wid).set(
                      float(proc["rss_bytes"]))
    compile_counts = (wm.get("device") or {}).get("compile")
    if isinstance(compile_counts, Mapping):
        _apply_table(reg, WORKER_COMPILE_TABLE, compile_counts,
                     WORKER_LABELS, {"worker_id": wid})
    models = wm.get("models")
    if isinstance(models, Mapping):
        for model, em in models.items():
            apply_engine(reg, em, model=str(model), worker_id=wid)
    pumps = wm.get("pumps")
    if isinstance(pumps, Mapping):
        for model, ps in pumps.items():
            apply_pump(reg, ps, model=str(model), worker_id=wid)
