"""Configuration tree for the framework.

Heir of the reference's ``src/config.py:12-20`` (a single ``ModelConfig``
dataclass) plus every constructor-knob cluster scattered through the reference
(batcher ``src/batcher.py:38-51``, router ``src/router.py:57-79``, load
balancer ``src/load_balancer.py:42-60``, cache ``src/kvstore.py:38-54``),
promoted into one typed config tree with a file loader — the config file the
reference README promised (``README.md:39`` names a ``demo_config.yaml`` that
never existed).

Everything is a frozen-ish dataclass so configs hash cleanly and can be passed
through jit boundaries as static arguments where needed.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import tomllib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


def build_dataclass(cls, d: Dict[str, Any]):
    """Construct ``cls`` from a dict, dropping unknown keys — the one shared
    deserialization rule for every config-ish dataclass in the framework."""
    known = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in d.items() if k in known})


@dataclass
class ModelConfig:
    """Per-model deployment config (reference ``src/config.py:12-20``).

    The reference carried name/path/batch-size/IO-schema; the TPU engine adds
    the fields a real model needs: architecture family, dtype, parallelism.
    """

    name: str
    path: str = ""                     # HF checkpoint dir (safetensors) or "" for random init
    version: str = "1.0"
    architecture: str = "fake"         # "fake" | "gpt2" | "llama"
    dtype: str = "bfloat16"
    batch_size: int = 1
    max_batch_size: int = 8
    max_seq_len: int = 2048
    quantized: bool = False
    input_schema: Dict[str, str] = field(default_factory=dict)
    output_schema: Dict[str, str] = field(default_factory=dict)
    metadata: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelConfig":
        return build_dataclass(cls, d)


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh axes. Axis order is (dp, pp, sp, tp) — outermost to
    innermost — so tensor-parallel collectives ride the fastest (ICI) links.

    ep (expert parallel) is folded onto the tp axis when unused; reserved as a
    first-class axis name for MoE models (SURVEY.md §2.3).
    """

    dp: int = 1      # data parallel (replica) axis
    pp: int = 1      # pipeline stage axis
    sp: int = 1      # sequence/context parallel axis (ring attention)
    tp: int = 1      # tensor parallel axis
    ep: int = 1      # expert parallel axis (MoE only)

    @property
    def n_devices(self) -> int:
        return self.dp * self.pp * self.sp * self.tp * self.ep

    def axis_sizes(self) -> Dict[str, int]:
        return {"dp": self.dp, "pp": self.pp, "sp": self.sp, "tp": self.tp, "ep": self.ep}


@dataclass
class EngineConfig:
    """Execution-engine knobs: shapes must be static for XLA (SURVEY.md §7
    hard-part #1), so every dynamic quantity is bucketed here."""

    max_seq_len: int = 2048
    max_slots: int = 8                 # concurrent sequences in the decode batch
    prefill_buckets: List[int] = field(default_factory=lambda: [128, 512, 2048])
    page_size: int = 128               # tokens per KV page (paged cache)
    num_pages: int = 512               # HBM page pool size
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    decode_steps_per_call: int = 8     # tokens generated per jit dispatch (lax.scan)
    attention_impl: str = "auto"       # "auto" | "xla" | "pallas-decode" |
    # "pallas-decode_interpret": which of its decode bodies a continuous
    # engine runs (engine.continuous.resolve_decode_body). "auto" takes the
    # in-place kernel ("window") on a TPU with an unsharded pool and Hkv*Dh
    # % 128 == 0, else XLA ("dense"); "_interpret" runs the kernel on a CPU.
    prefix_cache: bool = True          # reuse full KV pages across shared prompt prefixes
    kv_offload: bool = False           # host-RAM second tier for the paged
                                       # cache (engine/kv_offload.py):
                                       # evicted prefix pages offload
                                       # device->host instead of dropping,
                                       # admission prefetches host hits
                                       # back, and pool exhaustion swaps a
                                       # decode victim to host + resumes it
                                       # later instead of finishing it with
                                       # reason="length"
    kv_offload_bytes: int = 1 << 30    # host-tier byte budget (LRU store
                                       # + swap reservations share it)
    prefill_chunk: int = 0             # continuous engine: prompts longer than
                                       # this prefill in chunks interleaved with
                                       # decode (0 = whole-prompt prefill);
                                       # rounded to a multiple of page_size
    stream_chunk_steps: int = 0        # sub-chunk streaming (ISSUE 13):
                                       # while any live slot has a stream
                                       # callback, clamp decode chunks to
                                       # this many steps (pow2-bucketed —
                                       # at most ONE extra decode program)
                                       # so tokens reach the host
                                       # every few steps instead of once
                                       # per decode_steps_per_call
                                       # megastep. Pure-batch rounds keep
                                       # the full chunk. 0 = off.
    # ---- overload handling (continuous engine; VERDICT r2 item 2) ----
    max_waiting: int = 0               # waiting-queue cap: submit raises a
                                       # typed EngineOverloadedError once
                                       # this many requests are queued
                                       # (0 = unbounded)
    queue_deadline_s: float = 0.0      # shed requests still waiting for a
                                       # slot after this long: resolved as
                                       # finish_reason="overloaded" (pump/
                                       # RPC surface it as the typed error;
                                       # 0 = never shed)
    admission_max_rows: int = 0        # cap rows per admission-prefill
                                       # dispatch (0 = whole free-slot
                                       # set, the default). Historical
                                       # safety valve: the two-program
                                       # admission (prefill then page
                                       # write) held a [L, bb, T, Hkv,
                                       # Dh] x2 KV transient — ~2.1 GB at
                                       # 8B bb=128, a NONDETERMINISTIC
                                       # warmup OOM on 16 GB chips. The
                                       # fused prefill (per-layer KV
                                       # scattered into donated pools
                                       # inside the scan, models.base.
                                       # forward_prefill_into_pages)
                                       # removed the transient; the cap
                                       # remains for the sp path, which
                                       # keeps the two-program shape.
    timeline_capacity: int = 4096      # step-timeline ring buffer (obs/
                                       # timeline.py): per-dispatch records
                                       # kept for the Perfetto export; the
                                       # oldest fall off. 0 disables
                                       # recording entirely.


def validate_prefill_compose(prefill_chunk: int, sp: int = 1) -> None:
    """Reject prefill_chunk + sequence-parallel deploys with an actionable
    error — lifted out of ``ContinuousEngine.__init__`` so config loaders
    (``models.engine_from_config`` reads both knobs from model metadata)
    fail in milliseconds instead of after weights load. Both features bound
    the decode stall a long-prompt admission causes — chunking bounds it in
    TIME (prefill in page-aligned slices), sp bounds it in SPACE (shard the
    prompt across the mesh) — and the suffix-chunk programs are not
    sequence-parallel, so enabling both buys nothing and traces programs sp
    would never run.
    """
    if int(sp) > 1 and int(prefill_chunk) > 0:
        raise ValueError(
            "prefill_chunk and sp compose poorly: both bound the "
            "decode stall from long-prompt admission (chunking in "
            "time, sp in space), and the suffix-chunk programs are "
            "not sequence-parallel — pick one. Set prefill_chunk=0 "
            "to keep the sp mesh, or sp=1 to keep chunked prefill. "
            "Measured guidance (README, r3): chunking LOSES below "
            "multi-second admission stalls, so sp is the right pick "
            "for long-prompt deploys that have a mesh")


@dataclass
class BatcherConfig:
    """Reference ``src/batcher.py:38-51``: flush at max_batch_size OR after
    max_latency_ms, whichever first."""

    max_batch_size: int = 8
    max_latency_ms: float = 50.0
    pad_to_buckets: bool = True        # pad batches to power-of-two buckets for XLA


@dataclass
class CacheConfig:
    """Reference ``src/kvstore.py:38-54``."""

    max_size: int = 1024
    policy: str = "lru"                # "lru" | "lfu" | "fifo"
    default_ttl: Optional[float] = None
    # optional persistence (the reference README's declared-but-unbuilt
    # surface, ``/root/reference/README.md:14,90``): when set, the
    # coordinator restores the cache from this file at startup and
    # snapshots it alongside ``save_state``. Snapshots are JSON (non-
    # executable) by default; a pre-r3 pickle snapshot loads only with
    # persist_allow_pickle=True — the operator's acknowledgement that the
    # snapshot path is writable by them alone (unpickling runs code from
    # the file; ADVICE r2)
    persist_path: Optional[str] = None
    persist_allow_pickle: bool = False


@dataclass
class HealthConfig:
    """Reference ``src/router.py:57-79`` / ``src/load_balancer.py:42-60``:
    probe cadence + N-consecutive-failures threshold, extended with the
    per-worker circuit breaker the LB health loop drives (docs/design.md
    "Failure model")."""

    check_interval: float = 5.0
    check_timeout: float = 2.0
    max_consecutive_failures: int = 3
    enable_failover: bool = True
    # circuit breaker: after max_consecutive_failures the worker's circuit
    # OPENS (excluded from selection). The health loop waits out the
    # cooldown, then sends ONE half-open probe: success closes the
    # circuit, failure re-opens it and restarts the cooldown. 0.0 means
    # probe at the next health-loop tick (no extra wait).
    breaker_cooldown_s: float = 0.0


@dataclass
class ServerConfig:
    host: str = "127.0.0.1"
    port: int = 0                      # 0 = OS-assigned, like reference src/worker.py:58-59
    worker_id: str = "worker-0"
    request_timeout: float = 30.0      # reference src/worker.py:93
    max_frame_bytes: int = 64 * 1024 * 1024
    # multi-model residency budget (cluster/model_manager.py): how many
    # engines one worker may hold at once and/or their total parameter
    # bytes. Admission over either budget LRU-evicts idle models (never
    # ones with in-flight work). 0 = unbounded.
    max_resident_models: int = 0
    resident_bytes: int = 0
    # flight recorder (obs/events.py): bounded per-process typed event
    # ring collected over the ``events`` RPC verb
    event_ring_capacity: int = 2048


@dataclass
class AutoscalerConfig:
    """SLO-driven fleet sizing (cluster/autoscaler.py): the policy loop
    compares scrape-time TTFT/ITL percentiles and queue depth against
    these targets and grows/shrinks the replica set between
    ``min_workers`` and ``max_workers``. All decision state is tick-based
    (no wall-clock branches), so same-seed runs replay to an identical
    decision ledger."""

    # SLO targets: a dimension with target <= 0 is not enforced
    ttft_p95_target_s: float = 0.5
    itl_p95_target_s: float = 0.0
    queue_depth_target: float = 8.0   # mean waiting requests per worker
    # fleet bounds
    min_workers: int = 1
    max_workers: int = 4
    # hysteresis band on SLO attainment (1.0 = meeting every target):
    # below scale_up_attainment pressure is a breach; scale-down needs
    # attainment at scale_down_attainment AND queue drained below
    # scale_down_queue_frac * queue_depth_target. Between the bands the
    # policy holds.
    scale_up_attainment: float = 0.85
    scale_down_attainment: float = 1.0
    scale_down_queue_frac: float = 0.25
    # debounce: consecutive breach/clear ticks required before acting
    breach_ticks: int = 2
    clear_ticks: int = 4
    # cooldown windows (ticks) after a scale action before the next one
    cooldown_up_ticks: int = 3
    cooldown_down_ticks: int = 6
    # fleet-level graceful degradation: at max fleet and still breaching
    # for shed_ticks consecutive ticks, the coordinator sheds at
    # admission with the typed overloaded outcome + this retry-after hint
    shed_ticks: int = 4
    shed_retry_after_s: float = 1.0
    # policy loop cadence and victim tie-break seed
    interval_s: float = 0.5
    seed: int = 0
    # SLO burn-rate engine (obs/slo.py): when enabled, a multi-window
    # (fast + slow, tick-counted) error-budget burn evaluation over the
    # TTFT attainment window feeds the breach signal alongside the
    # attainment band. Burn = (bad/total) / (1 - goal); a breach needs
    # BOTH windows at or above the threshold.
    slo_burn_enabled: bool = False
    slo_burn_goal: float = 0.9        # fraction of requests under target
    slo_burn_fast_ticks: int = 10
    slo_burn_slow_ticks: int = 120
    slo_burn_threshold: float = 1.0


@dataclass
class MultihostConfig:
    """jax.distributed bootstrap for pod slices (parallel/multihost.py);
    empty/default fields mean Cloud-TPU env auto-discovery."""

    enabled: bool = False
    coordinator_address: str = ""     # host:port; "" = auto-discover
    num_processes: int = 0            # 0 = auto
    process_id: int = -1              # -1 = auto


@dataclass
class Config:
    """Root config: engine/mesh/serving/cluster sections (SURVEY.md §5
    config-system plan)."""

    models: List[ModelConfig] = field(default_factory=list)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    batcher: BatcherConfig = field(default_factory=BatcherConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    health: HealthConfig = field(default_factory=HealthConfig)
    server: ServerConfig = field(default_factory=ServerConfig)
    multihost: MultihostConfig = field(default_factory=MultihostConfig)
    autoscaler: AutoscalerConfig = field(default_factory=AutoscalerConfig)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def config_from_dict(d: Dict[str, Any]) -> Config:
    cfg = Config()
    if "models" in d:
        cfg.models = [ModelConfig.from_dict(m) for m in d["models"]]
    for section, cls in (
        ("mesh", MeshConfig),
        ("engine", EngineConfig),
        ("batcher", BatcherConfig),
        ("cache", CacheConfig),
        ("health", HealthConfig),
        ("server", ServerConfig),
        ("multihost", MultihostConfig),
        ("autoscaler", AutoscalerConfig),
    ):
        if section in d:
            setattr(cfg, section, build_dataclass(cls, d[section]))
    return cfg


def load_config(path: str) -> Config:
    """Load a Config from JSON, TOML, or YAML by extension."""
    p = pathlib.Path(path)
    text = p.read_text()
    if p.suffix in (".json",):
        data = json.loads(text)
    elif p.suffix in (".toml",):
        data = tomllib.loads(text)
    elif p.suffix in (".yaml", ".yml"):
        import yaml

        data = yaml.safe_load(text)
    else:
        raise ValueError(f"unsupported config extension: {p.suffix}")
    return config_from_dict(data or {})
