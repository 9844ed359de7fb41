import logging

from .base import (  # noqa: F401
    ModelSpec,
    init_params,
    forward_prefill,
    forward_decode,
    forward_train,
    causal_lm_loss,
    embed,
    unembed,
)
from .gpt2 import gpt2_spec  # noqa: F401
from .llama import llama_spec, mixtral_spec  # noqa: F401
from .qwen import qwen_spec  # noqa: F401
from .mistral import mistral_spec  # noqa: F401
from .gemma import gemma_spec  # noqa: F401
from .ling import ling_spec  # noqa: F401
from .xing import kimi_spec, xing_spec  # noqa: F401
from .olmo_hybrid import olmo_hybrid_spec  # noqa: F401
from .mellum import mellum_spec  # noqa: F401
from .keye import keye_spec  # noqa: F401
from .fake import FakeContinuousEngine, FakeEngine, FakePrefillEngine  # noqa: F401

logger = logging.getLogger(__name__)

# family prefix -> (spec factory, default size). Sizes live in each family
# module; architecture strings like "qwen2-7b" select the size directly.
_FAMILIES = {
    "qwen": (qwen_spec, "qwen2-7b"),
    "mistral": (mistral_spec, "mistral-7b"),
    "gemma": (gemma_spec, "gemma-7b"),
    "mixtral": (mixtral_spec, "mixtral-8x7b"),
    "llama": (llama_spec, "llama3-8b"),
    "ling": (ling_spec, "ling-3.0-flash-ep4"),
    "xing": (xing_spec, "xing4.0-pp1"),
    "kimi": (kimi_spec, "kimi-k2.5-ep32-pp1"),
    "olmo_hybrid": (olmo_hybrid_spec, "olmo-hybrid-7b-pp2"),
    "mellum": (mellum_spec, "mellum2-12b-a2.5b-pp1"),
    "keye": (keye_spec, "keye-vl-2.0-30b-a3b-pp1"),
}


def build_engine(architecture: str, **kwargs):
    """Engine factory keyed by ``ModelConfig.architecture``.

    Accepts the union of fake-engine and real-engine knobs and routes each
    branch only what it understands, so one config-driven call site works
    across architectures."""
    fake_keys = ("latency_s", "per_token_latency_s", "error_rate", "seed")
    if architecture == "fake":
        return FakeEngine(**{k: v for k, v in kwargs.items() if k in fake_keys})
    from ..engine.engine import Engine

    spec = spec_for_architecture(architecture)
    real_keys = ("params", "config", "seed", "shard_fn")
    return Engine(spec, **{k: v for k, v in kwargs.items() if k in real_keys})


def spec_for_architecture(architecture: str, size: str = "",
                          max_seq_len: int = 0):
    """One spec-selection rule for every call site (keyword factory above,
    config-driven factory below) so matching can't drift."""
    overrides = {"max_seq_len": max_seq_len} if max_seq_len else {}
    if architecture.startswith("gpt2"):
        # unknown sizes raise in gpt2_spec — a typo'd deploy must fail
        # loudly, not silently serve the 124M default
        return gpt2_spec(size or architecture, **overrides)
    for prefix, (factory, default) in _FAMILIES.items():
        if architecture.startswith(prefix):
            name = size or (architecture if "-" in architecture else default)
            return factory(name, **overrides)
    raise ValueError(f"unknown architecture {architecture!r}")


# Metadata a deploy may still carry from before PR 29. Outside input is
# checked, never silently ignored: each raises at load, by name.
_RETIRED_KEYS = {
    "decode_mode": "derived: inline if and only if the spec has a sliding "
                   "window",
    "mixed_step_tokens": "the ragged mixed step is gone (Mosaic refused its "
                         "kernel on the chip); prefill_chunk alone chunks",
    "spec_async": "async speculation is gone; metadata speculative=K "
                  "selects the speculative engine",
    "spec_draft_model": "async speculation is gone",
    "spec_max_draft": "async speculation is gone",
    "spec_bubble_floor_s": "async speculation is gone",
}
_RETIRED_ATTENTION = {
    "pallas": "pallas-decode reads the page pool in place",
    "pallas-decode-fw": "Mosaic refused the epilogue write on the chip",
    "pallas-ragged": "Mosaic refused the kernel on the chip",
}


def _check_retired(metadata) -> None:
    for key, why in _RETIRED_KEYS.items():
        if key in metadata:
            raise ValueError(f"metadata key {key!r} is retired: {why}")
    impl = str(metadata.get("attention_impl", ""))
    why = _RETIRED_ATTENTION.get(impl.removesuffix("_interpret"))
    if why:
        raise ValueError(
            f"metadata attention_impl {impl!r} is retired: {why}; use "
            "'auto', 'xla', 'pallas-decode' or 'pallas-decode_interpret'")


def engine_from_config(cfg):
    """``ModelConfig`` → engine: the worker-side factory (replaces the
    reference's hard-wired ``FakeModel(config)``, ``src/worker.py:171``).
    Loads HF safetensors when ``cfg.path`` is a checkpoint dir, else random
    init — enough for perf work and smoke tests."""
    import os

    _check_retired(cfg.metadata)
    arch = cfg.architecture.lower()
    if arch == "fake":
        # load_sleep_s models the checkpoint-read + prepare cost a real
        # cold start pays: a cold load_model eats it on the caller's
        # clock, a background stage (cluster/model_manager.py) eats it on
        # a side thread — the staged-swap-vs-cold-load receipts the
        # multimodel fleet leg measures need a nonzero gap to compare
        load_sleep = float(cfg.metadata.get("load_sleep_s", 0) or 0)
        if load_sleep:
            import time

            time.sleep(load_sleep)
        if cfg.metadata.get("role") == "prefill":
            # prefill-pool fake: chain-consistent handoffs over the real
            # wire format, so disaggregated fleets test jax-free
            return FakePrefillEngine(
                latency_s=float(cfg.metadata.get("latency_s", 0.0)),
                per_token_latency_s=float(
                    cfg.metadata.get("per_token_latency_s", 0.0)),
                max_seq_len=int(cfg.max_seq_len),
            )
        if cfg.metadata.get("continuous"):
            # continuous fake: submit/step interface, so the worker builds
            # an EnginePump around it — streaming, deadlines, and drain
            # become testable on a jax-free multi-worker fleet
            return FakeContinuousEngine(
                step_latency_s=float(cfg.metadata.get("step_latency_s", 0.0)),
                tokens_per_step=int(cfg.metadata.get("tokens_per_step", 1)),
                max_slots=int(cfg.metadata.get("max_slots", 8)),
                max_waiting=int(cfg.metadata.get("max_waiting", 0)),
                queue_deadline_s=float(
                    cfg.metadata.get("queue_deadline_s", 0.0)),
                vocab_size=int(cfg.metadata.get("vocab_size", 997)),
                admit_latency_per_token_s=float(
                    cfg.metadata.get("admit_latency_per_token_s", 0.0)),
                prefix_cache=bool(cfg.metadata.get("prefix_cache", False)),
                prefix_page_size=int(
                    cfg.metadata.get("prefix_page_size", 64)),
                stream_chunk_tokens=int(
                    cfg.metadata.get("stream_chunk_tokens", 0)),
                stream_dispatch_overhead_s=float(
                    cfg.metadata.get("stream_dispatch_overhead_s", 0.0)),
            )
        return FakeEngine(
            latency_s=float(cfg.metadata.get("latency_s", 0.0)),
            per_token_latency_s=float(cfg.metadata.get("per_token_latency_s", 0.0)),
            error_rate=float(cfg.metadata.get("error_rate", 0.0)),
        )

    from ..config import EngineConfig
    from ..engine.engine import Engine
    from .base import init_params
    from .loader import load_checkpoint, spec_from_hf_config

    spec = spec_for_architecture(arch, size=cfg.metadata.get("size", ""),
                                 max_seq_len=cfg.max_seq_len)

    # parallel-placement metadata: validate BEFORE the (expensive)
    # checkpoint load/quantize so a bad deploy fails in milliseconds, not
    # after minutes of safetensors reads on a large model
    tp = int(cfg.metadata.get("tp", 1))
    sp = int(cfg.metadata.get("sp", 1))
    dp = int(cfg.metadata.get("dp", 1))
    # sp + chunked prefill compose poorly — reject the pair here, before
    # the checkpoint load, with the same actionable message the engine
    # raises (config.validate_prefill_compose)
    from ..config import validate_prefill_compose

    validate_prefill_compose(
        int(cfg.metadata.get("prefill_chunk", 0) or 0), sp=sp)
    want_mesh = tp > 1 or sp > 1 or dp > 1
    if want_mesh:
        import jax as _jax

        if int(cfg.metadata.get("speculative", 0)) and (sp > 1 or dp > 1):
            raise ValueError(
                "speculative decoding composes with tp only (target "
                "sharded, draft replicated); sp/dp shard the prefill "
                "batch/sequence, which the speculative window forwards "
                "do not — drop sp/dp or deploy replicas via the load "
                "balancer")
        if dp > 1 and sp <= 1:
            raise ValueError(
                "dp metadata only composes with sp (the sequence-parallel "
                "prefill shards its batch over dp); nothing in the tp-only "
                "serving path shards over dp — drop dp or deploy replicas "
                "via the load balancer instead")
        need = dp * sp * tp
        devs = _jax.devices()
        if len(devs) < need:
            raise ValueError(
                f"deploy requests mesh dp={dp} sp={sp} tp={tp} "
                f"({need} devices) but only {len(devs)} are visible")
    ecfg = EngineConfig(max_slots=cfg.max_batch_size,
                        max_seq_len=cfg.max_seq_len)
    for k in ("page_size", "num_pages", "decode_steps_per_call",
              "attention_impl", "kv_dtype", "prefill_buckets",
              "prefix_cache", "prefill_chunk",
              "max_waiting", "queue_deadline_s",
              "kv_offload", "kv_offload_bytes",
              "stream_chunk_steps", "admission_max_rows",
              "timeline_capacity"):
        if k in cfg.metadata:
            setattr(ecfg, k, cfg.metadata[k])
    if spec.layer_kinds:
        return _hybrid_engine(cfg, spec, ecfg)

    # ---- pre-fused serving artifact (engine/artifact.py): the elastic
    # fast path. metadata artifact=<dir> restores the post-quantize/fuse/
    # pad tree — skipping the minutes-scale init a respawned worker would
    # otherwise re-pay — and the golden-token self-check gates admission.
    # Single-host Engine/ContinuousEngine only: mesh deploys re-resolve
    # kernel modes against the sharding, and the speculative/prefill
    # engines carry extra state the artifact does not capture.
    spec_k = int(cfg.metadata.get("speculative", 0))
    art = str(cfg.metadata.get("artifact", "") or "")
    art_required = bool(int(cfg.metadata.get("artifact_required", 0) or 0))
    art_selfcheck = bool(int(cfg.metadata.get("artifact_selfcheck", 1)))
    art_eligible = (bool(art) and not want_mesh and not spec_k
                    and cfg.metadata.get("role") != "prefill")
    if art and not art_eligible:
        if art_required:
            raise ValueError(
                "artifact_required is set but this deploy is not "
                "artifact-eligible: mesh/speculative/prefill engines "
                "cannot cold-start from a serving artifact")
        logger.warning(
            "artifact metadata ignored for model %s: only single-host "
            "Engine/ContinuousEngine deploys cold-start from artifacts",
            cfg.name)
    if art_eligible:
        from ..engine.artifact import (
            ArtifactCorruptError,
            ArtifactError,
            ArtifactMismatchError,
            feature_hash,
            has_artifact,
            load_manifest,
        )

        if has_artifact(art):
            try:
                manifest = load_manifest(art)
                if (manifest["feature_hash"]
                        and manifest["feature_hash"] != feature_hash(cfg)):
                    raise ArtifactMismatchError(
                        f"artifact {art} was built for a different deploy "
                        "config (feature hash differs) — refusing to "
                        "serve it")
                if cfg.metadata.get("continuous"):
                    from ..engine.continuous import ContinuousEngine

                    return ContinuousEngine(
                        None, config=ecfg, artifact_path=art,
                        artifact_selfcheck=art_selfcheck)
                return Engine(None, config=ecfg, artifact_path=art,
                              artifact_selfcheck=art_selfcheck)
            except ArtifactError as e:
                if art_required:
                    raise
                logger.warning(
                    "artifact %s rejected (%s: %s) — falling back to "
                    "from-scratch init and rewriting it", art,
                    type(e).__name__, e)
        elif art_required:
            raise ArtifactCorruptError(
                f"artifact_required is set but no committed artifact "
                f"exists at {art}")
    from ..utils.checkpoint import is_native_checkpoint, load_params, load_spec

    built = None                       # (mesh, ModelShardings) once built

    def _build_shardings(final_spec):
        from ..parallel.mesh import make_mesh
        from ..parallel.sharding import ModelShardings
        from ..config import MeshConfig
        import jax as _jax

        mesh = make_mesh(MeshConfig(dp=dp, sp=sp, tp=tp),
                         _jax.devices()[: dp * sp * tp])
        return mesh, ModelShardings.build(final_spec, mesh)

    if cfg.path and is_native_checkpoint(cfg.path):
        # our own Orbax checkpoint dir (utils/checkpoint.py): spec sidecar
        # + params tree, no HF mapping needed; the sidecar's dtype is
        # authoritative (params are stored in it)
        ck_spec = load_spec(cfg.path)
        spec = ck_spec.replace(max_seq_len=min(cfg.max_seq_len,
                                               ck_spec.max_seq_len))
        if want_mesh:
            # restore DIRECTLY into the mesh layout: loading the full tree
            # onto one device and resharding after would peak at the whole
            # model's bytes on a single chip
            import jax as _jax

            built = _build_shardings(spec)      # reused by the engine below
            abstract = _jax.eval_shape(
                lambda: init_params(spec, _jax.random.key(0)))
            template = _jax.tree.map(
                lambda a, sh: _jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=sh),
                abstract, built[1].params)
            params = load_params(cfg.path, template=template)
        else:
            params = load_params(cfg.path)
    elif cfg.path and os.path.isdir(cfg.path):
        hf_spec = spec_from_hf_config(cfg.path)
        spec = hf_spec.replace(max_seq_len=min(cfg.max_seq_len,
                                               hf_spec.max_seq_len),
                               dtype=cfg.dtype or hf_spec.dtype)
        params = load_checkpoint(cfg.path, spec)
    else:
        # honor the deploy config's compute dtype (previously silently
        # ignored: a dtype=float32 deploy got the family default)
        if cfg.dtype:
            spec = spec.replace(dtype=cfg.dtype)
        params = None
    if cfg.quantized:
        # weight-only int8 (ops/quant.py): the registry's `quantized` flag,
        # made real — halves decode's HBM weight traffic
        import jax as _jax

        from ..ops.quant import quantize_params, random_quantized_params

        # metadata.weight_bits=4 selects packed-nibble int4 (half the int8
        # stream again); default 8
        bits = int(cfg.metadata.get("weight_bits", 8))
        if params is None:
            # direct quantized init: init-then-quantize would peak at the
            # full bf16 tree + f32 working copies — OOM at exactly the
            # 8B-on-one-chip deploys the quantized flag exists for
            params = random_quantized_params(
                spec, _jax.random.key(int(cfg.metadata.get("seed", 0))),
                bits=bits)
        else:
            params = quantize_params(spec, params, bits=bits)
    # config-driven parallel serving: build the mesh + shardings from the
    # validated metadata so a plain deploy config (CLI flag, coordinator
    # deploy_model, config file) can request tensor-/sequence-parallel
    # placement — no programmatic mesh plumbing needed
    shard_fn = None
    kv_sharding = None
    sp_mesh = None
    if want_mesh:
        if built is None:
            built = _build_shardings(spec)
        mesh, shardings = built
        shard_fn = shardings.shard_fn()
        kv_sharding = shardings.paged_kv
        if sp > 1:
            sp_mesh = mesh
    if spec_k:
        # draft-model speculative decoding (engine/speculative.py):
        # metadata speculative=K, draft_size=<spec name>, optional
        # draft_path=<HF checkpoint dir>
        from ..engine.speculative import SpeculativeEngine

        draft_size = cfg.metadata.get("draft_size", "")
        if not draft_size and not cfg.metadata.get("draft_path"):
            raise ValueError(
                "speculative decoding needs metadata draft_size and/or "
                "draft_path")
        draft_path = cfg.metadata.get("draft_path", "")
        if draft_path and not os.path.isdir(draft_path):
            # a typo'd/unmounted checkpoint must not silently fall back to
            # a random-weight draft (≈0% acceptance ⇒ slower than plain)
            raise ValueError(
                f"draft_path {draft_path!r} is not a directory")
        if draft_path:
            d_spec = spec_from_hf_config(draft_path)
            d_spec = d_spec.replace(max_seq_len=min(cfg.max_seq_len,
                                                    d_spec.max_seq_len))
            d_params = load_checkpoint(draft_path, d_spec)
        else:
            d_spec = spec_for_architecture(arch, size=draft_size,
                                           max_seq_len=cfg.max_seq_len)
            if cfg.dtype:
                d_spec = d_spec.replace(dtype=cfg.dtype)
            d_params = None
        # dense [L,B,S,Hkv,Dh] target-cache sharding (shardings was built
        # alongside shard_fn above whenever a mesh was requested)
        spec_kv = shardings.kv if want_mesh else None
        return SpeculativeEngine(spec, d_spec, params=params,
                                 draft_params=d_params, config=ecfg,
                                 speculate_k=spec_k, shard_fn=shard_fn,
                                 kv_sharding=spec_kv)
    if cfg.metadata.get("role") == "prefill":
        # disaggregated prefill pool: prefill-only engine (engine/disagg.py);
        # sp here gives the pool sequence-parallel ring-attention prefill
        from ..engine.disagg import PrefillEngine

        return PrefillEngine(spec, params=params, config=ecfg,
                             shard_fn=shard_fn, sp_mesh=sp_mesh)
    if cfg.metadata.get("continuous"):
        from ..engine.continuous import ContinuousEngine

        eng = ContinuousEngine(spec, params=params, config=ecfg,
                               shard_fn=shard_fn, kv_sharding=kv_sharding,
                               sp_mesh=sp_mesh)
    else:
        eng = Engine(spec, params=params, config=ecfg, shard_fn=shard_fn,
                     sp_mesh=sp_mesh)
    if art_eligible:
        # elastic flow: the first (slow) boot commits the prepared tree so
        # every subsequent respawn cold-starts from it in seconds
        _refresh_artifact(art, cfg, eng, probe=art_selfcheck)
    return eng


def _hybrid_engine(cfg, spec, ecfg):
    """A per-layer (hybrid) spec, ``models/ling.py``, ``models/xing.py``,
    ``models/olmo_hybrid.py``, ``models/mellum.py`` or ``models/keye.py``
    (``models/base.py`` ``layered_family`` tells them apart): served by the
    continuous engine from a random tree keyed by ``metadata.seed``. What it
    cannot do yet fails here, before any weight exists; the engine refuses
    the ``ecfg`` keys it cannot honour for such a spec (kv_offload,
    prefill_chunk, a Pallas attention_impl)."""
    from ..engine.continuous import ContinuousEngine

    md = cfg.metadata
    refused = [name for name, on in (
        ("a checkpoint path (no loader for this model_type)", bool(cfg.path)),
        ("quantized weights (int8/int4 trees)", bool(cfg.quantized)),
        ("a tp/sp/dp mesh", any(int(md.get(k, 1)) > 1
                                for k in ("tp", "sp", "dp"))),
        ("speculative decoding", bool(int(md.get("speculative", 0)))),
        ("role=prefill (disaggregated serving)", md.get("role") == "prefill"),
        ("a serving artifact", bool(md.get("artifact"))),
        ("the static engine (metadata continuous=1 is required)",
         not md.get("continuous")),
    ) if on]
    if refused:
        raise ValueError(f"architecture {cfg.architecture!r} does not "
                         "support: " + "; ".join(refused))
    if cfg.dtype:
        spec = spec.replace(dtype=cfg.dtype)
    return ContinuousEngine(spec, config=ecfg, seed=int(md.get("seed", 0)))


def _refresh_artifact(path: str, cfg, engine, probe: bool = True) -> None:
    """Best-effort artifact (re)write after a slow-path init. Failure is
    logged, never fatal — the engine just built is healthy regardless; the
    next boot simply pays the slow path again."""
    from ..engine.artifact import save_artifact
    from ..engine.engine import _pow2_buckets

    try:
        buckets = {
            "batch": [int(x) for x in
                      (getattr(engine, "batch_buckets", None)
                       or _pow2_buckets(engine.max_slots))],
            "prefill": [int(x) for x in
                        getattr(engine, "prefill_buckets", [])],
            "seq": [int(x) for x in getattr(engine, "seq_buckets", [])],
        }
        save_artifact(path, engine.spec, engine.params, cfg=cfg,
                      buckets=buckets, engine=engine if probe else None)
    # graftlint: ok[swallowed-transport-error] local best-effort persistence, no peer involved; the slow-path engine serves either way
    except Exception:
        logger.exception(
            "serving-artifact write to %s failed — serving from the "
            "slow-path engine anyway", path)
