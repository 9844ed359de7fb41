"""Elastic-lifecycle tests: pre-fused serving artifacts (save/load
bit-parity, the manifest-last commit point, three-layer validation with
typed fallback, the measured cold-start win) and the coordinator's
supervised auto-respawn loop (respawn + half-open rejoin, crash-loop
breaker with surviving replicas).

The artifact half runs real llama-tiny engines on CPU; the supervisor
half is jax-free (architecture="fake" workers) so the control-plane
semantics are tested at millisecond cadence.
"""

import asyncio
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from distributed_inference_engine_tpu.api.coordinator import (
    Coordinator,
    CoordinatorConfig,
)
from distributed_inference_engine_tpu.cluster.load_balancer import (
    BREAKER_OPEN,
)
from distributed_inference_engine_tpu.cluster.registry import ModelStatus
from distributed_inference_engine_tpu.cluster.worker import WorkerServer
from distributed_inference_engine_tpu.config import (
    HealthConfig,
    ModelConfig,
    ServerConfig,
)
from distributed_inference_engine_tpu.engine.artifact import (
    ArtifactCorruptError,
    ArtifactMismatchError,
    MANIFEST_FILE,
    feature_hash,
    has_artifact,
    load_artifact,
    load_manifest,
    save_artifact,
    tree_checksum,
    write_manifest,
)
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models import engine_from_config
from distributed_inference_engine_tpu.models.llama import llama_spec
from distributed_inference_engine_tpu.utils import checkpoint

pytestmark = pytest.mark.elastic


def _spec(dtype="float32"):
    return llama_spec("llama-tiny", max_seq_len=64, dtype=dtype)


def _cfg(art_dir, *, dtype="float32", quantized=False, bits=8, **meta):
    md = {"size": "llama-tiny", "artifact": str(art_dir)}
    if quantized:
        md["weight_bits"] = bits
    md.update(meta)
    return ModelConfig(name="m", architecture="llama", dtype=dtype,
                       max_seq_len=64, max_batch_size=2,
                       quantized=quantized, metadata=md)


def _greedy(engine, prompt=(4, 9, 2), n=6):
    return engine.generate([GenerationRequest(
        prompt=list(prompt), max_new_tokens=n, temperature=0.0)])[0].tokens


def _sampled(engine, prompt=(4, 9, 2), n=6):
    return engine.generate([GenerationRequest(
        prompt=list(prompt), max_new_tokens=n, temperature=0.8,
        top_k=16)])[0].tokens


# ------------------------------------------------- save/load bit parity

@pytest.mark.parametrize("mode", ["f32", "bf16", "int8", "int4"])
def test_artifact_tree_roundtrip_bitexact(tmp_path, mode):
    """Every leaf — including packed int4 q/s pairs — survives the
    artifact round trip bit-for-bit, and the checksum layer agrees."""
    import jax
    import numpy as np

    from distributed_inference_engine_tpu.models.base import init_params
    from distributed_inference_engine_tpu.ops.quant import quantize_params

    dtype = "bfloat16" if mode == "bf16" else "float32"
    spec = _spec(dtype)
    params = init_params(spec, jax.random.key(0))
    if mode in ("int8", "int4"):
        params = quantize_params(spec, params,
                                 bits=4 if mode == "int4" else 8)
    path = save_artifact(str(tmp_path / "art"), spec, params)
    assert has_artifact(path)
    spec2, restored, manifest = load_artifact(path)
    assert spec2.to_dict() == spec.to_dict()
    assert manifest["checksum"] == tree_checksum(restored)
    if mode in ("int8", "int4"):
        bits = 4 if mode == "int4" else 8
        assert manifest["quant"].get(f"int{bits}", 0) > 0
    a_leaves = jax.tree_util.tree_leaves(params)
    b_leaves = jax.tree_util.tree_leaves(restored)
    assert len(a_leaves) == len(b_leaves)
    for a, b in zip(a_leaves, b_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a).view("uint8"), np.asarray(b).view("uint8"))


@pytest.mark.parametrize("quant", ["f32", "int4"])
def test_factory_cold_start_token_identical(tmp_path, quant):
    """The end-to-end contract: an engine cold-started from the artifact
    produces the SAME tokens as the slow-path engine that wrote it —
    greedy and sampled, int4 included."""
    art = tmp_path / "art"
    cfg = _cfg(art, quantized=(quant == "int4"), bits=4)
    slow = engine_from_config(cfg)
    assert has_artifact(str(art)), "slow-path build must commit an artifact"
    fast = engine_from_config(cfg)
    assert getattr(fast, "artifact_manifest", None) is not None, \
        "second build must cold-start from the artifact"
    assert _greedy(fast) == _greedy(slow)
    assert _sampled(fast) == _sampled(slow)


# ------------------------------------------- validation + commit point

def test_feature_hash_mismatch_rejected(tmp_path):
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    art = str(tmp_path / "art")
    cfg = _cfg(tmp_path / "art")
    spec = _spec()
    save_artifact(art, spec, init_params(spec, jax.random.key(0)), cfg=cfg)
    drifted = _cfg(tmp_path / "art", seed=99)
    assert feature_hash(drifted) != feature_hash(cfg)
    with pytest.raises(ArtifactMismatchError):
        load_artifact(art, cfg=drifted)
    # same identity still loads
    load_artifact(art, cfg=cfg)


def test_factory_rewrites_mismatched_artifact(tmp_path):
    """Config drift at the factory: the stale artifact is ignored (slow
    path) and REWRITTEN for the new identity — next boot is fast again."""
    art = tmp_path / "art"
    engine_from_config(_cfg(art))
    old_hash = load_manifest(str(art))["feature_hash"]
    drifted = _cfg(art, seed=99)
    eng = engine_from_config(drifted)           # falls back, no raise
    assert getattr(eng, "artifact_manifest", None) is None
    assert load_manifest(str(art))["feature_hash"] == feature_hash(drifted)
    assert load_manifest(str(art))["feature_hash"] != old_hash
    # artifact_required=1 makes the mismatch fatal instead
    required = _cfg(art, seed=7, artifact_required=1)
    with pytest.raises(ArtifactMismatchError):
        engine_from_config(required)


def test_truncated_and_bitflipped_params_rejected(tmp_path):
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    art = str(tmp_path / "art")
    spec = _spec()
    save_artifact(art, spec, init_params(spec, jax.random.key(0)))
    # largest file under params/ is certainly weight bytes
    files = sorted(pathlib.Path(art).joinpath("params").rglob("*"),
                   key=lambda p: p.stat().st_size if p.is_file() else 0)
    victim = files[-1]
    blob = victim.read_bytes()
    assert len(blob) > 64
    victim.write_bytes(blob[: len(blob) // 2])          # truncation
    with pytest.raises(ArtifactCorruptError):
        load_artifact(art)
    flipped = bytearray(blob)
    flipped[len(flipped) // 2] ^= 0xFF                  # single flipped byte
    victim.write_bytes(bytes(flipped))
    with pytest.raises(ArtifactCorruptError):
        load_artifact(art)


def test_manifest_is_the_commit_point(tmp_path):
    """A crash mid-save leaves params without a manifest — treated as
    absent, and the factory quietly rebuilds + commits."""
    import jax

    from distributed_inference_engine_tpu.models.base import init_params

    art = tmp_path / "art"
    spec = _spec()
    # simulate the crash: params land, the manifest never does
    checkpoint.save_params(str(art), spec,
                           init_params(spec, jax.random.key(0)))
    assert not has_artifact(str(art))
    with pytest.raises(ArtifactCorruptError):
        load_manifest(str(art))
    eng = engine_from_config(_cfg(art))
    assert getattr(eng, "artifact_manifest", None) is None   # slow path
    assert has_artifact(str(art))                            # now committed
    # a truncated manifest (torn write outside atomic_write) is corrupt,
    # version drift likewise
    (art / MANIFEST_FILE).write_text("{")
    with pytest.raises(ArtifactCorruptError):
        load_manifest(str(art))
    write_manifest(str(art), {"version": 999, "checksum": "x",
                              "feature_hash": ""})
    with pytest.raises(ArtifactCorruptError):
        load_manifest(str(art))


def test_golden_probe_failure_falls_back(tmp_path):
    """Wrong numerics behind a valid checksum (the case only the probe
    can catch): the self-check raises, the factory serves the slow path,
    and artifact_required=1 surfaces the typed error instead."""
    art = tmp_path / "art"
    slow = engine_from_config(_cfg(art))
    want = _greedy(slow)
    manifest = load_manifest(str(art))
    assert manifest["golden"], "factory saves must record a golden probe"
    manifest["golden"]["tokens"] = [
        (t + 1) % 50257 for t in manifest["golden"]["tokens"]]
    write_manifest(str(art), manifest)
    eng = engine_from_config(_cfg(art))
    assert getattr(eng, "artifact_manifest", None) is None   # fell back
    assert _greedy(eng) == want                              # still correct
    # ...and the fallback REWROTE the artifact with a fresh golden, so
    # the next boot is fast again
    assert load_manifest(str(art))["golden"]["tokens"] != \
        manifest["golden"]["tokens"]
    fast = engine_from_config(_cfg(art))
    assert getattr(fast, "artifact_manifest", None) is not None
    # with artifact_required=1 the same corruption is fatal instead
    bad = load_manifest(str(art))
    bad["golden"]["tokens"] = [(t + 1) % 50257
                               for t in bad["golden"]["tokens"]]
    write_manifest(str(art), bad)
    with pytest.raises(ArtifactCorruptError):
        engine_from_config(_cfg(art, artifact_required=1,
                                artifact_selfcheck=1))


def test_artifact_skips_probe_when_selfcheck_off(tmp_path):
    art = tmp_path / "art"
    cfg = _cfg(art, artifact_selfcheck=0)
    slow = engine_from_config(cfg)
    assert load_manifest(str(art))["golden"] is None
    fast = engine_from_config(cfg)
    assert getattr(fast, "artifact_manifest", None) is not None
    assert _greedy(fast) == _greedy(slow)


# ---------------------------------------------------- cold-start steps

# Each boot runs in a fresh interpreter: a cold start IS a fresh process.
# What the artifact buys is counted, not timed: a wall-clock ratio on the
# CPU proxy measures XLA's CPU backend (it read 2.9x to 10x across jax
# versions with no change to this code). The script wraps the init and
# prepare entry points and reports how often each ran.
_BOOT_SCRIPT = """\
import json, sys
sys.path.insert(0, sys.argv[2])
from distributed_inference_engine_tpu.config import ModelConfig
from distributed_inference_engine_tpu.engine.types import GenerationRequest
from distributed_inference_engine_tpu.models import engine_from_config
from distributed_inference_engine_tpu.ops import quant

calls = {"random_quantized_params": 0, "prepare_params": 0}
for _name in calls:
    def _counted(*a, _fn=getattr(quant, _name), _name=_name, **kw):
        calls[_name] += 1
        return _fn(*a, **kw)
    setattr(quant, _name, _counted)

cfg = ModelConfig(
    name="m", architecture="llama", dtype="float32", max_seq_len=64,
    max_batch_size=2, quantized=True,
    metadata={"size": "llama-tiny", "artifact": sys.argv[1],
              "weight_bits": 4, "artifact_selfcheck": 0})
eng = engine_from_config(cfg)
toks = eng.generate([GenerationRequest(
    prompt=[4, 9, 2], max_new_tokens=6, temperature=0.0)])[0].tokens
print(json.dumps({"calls": calls, "greedy": toks,
                  "artifact": getattr(eng, "artifact_manifest", None)
                  is not None}))
"""


def _boot_fresh_process(script, art):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = str(pathlib.Path(__file__).resolve().parents[1])
    out = subprocess.run(
        [sys.executable, str(script), str(art), repo],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cold_start_skips_init_and_prepare(tmp_path):
    """Process-cold on both sides: the slow path runs the quantized init
    and prepare_params (mode stamp + fuse) once each; the artifact boot
    runs NEITHER and decodes the same greedy tokens. Set-up time on the
    chip is reported by ``chip_smoke.py``, not asserted here."""
    art = tmp_path / "art"
    script = tmp_path / "boot.py"
    script.write_text(_BOOT_SCRIPT)
    slow = _boot_fresh_process(script, art)
    assert not slow["artifact"]
    assert slow["calls"] == {"random_quantized_params": 1,
                             "prepare_params": 1}
    assert has_artifact(str(art))
    fast = _boot_fresh_process(script, art)
    assert fast["artifact"]
    assert fast["calls"] == {"random_quantized_params": 0,
                             "prepare_params": 0}
    assert fast["greedy"] == slow["greedy"]


# ------------------------------------------------- supervisor (jax-free)

def _coord_cfg(**over):
    kw = dict(
        health=HealthConfig(check_interval=0.05, check_timeout=0.5,
                            max_consecutive_failures=2),
        retry_seed=7, retry_backoff_base_s=0.01,
        supervisor_interval_s=0.05, supervisor_backoff_base_s=0.01,
        supervisor_backoff_max_s=0.05, supervisor_load_timeout_s=10.0,
    )
    kw.update(over)
    return CoordinatorConfig(**kw)


async def _wait_for(pred, timeout=20.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        await asyncio.sleep(0.02)
    return False


@pytest.mark.chaos
async def test_supervisor_respawns_dead_worker():
    """Hard-kill one of two fake workers: the health loop flags it, the
    supervisor's restart hook brings a replacement up under the SAME id,
    the model is reloaded, and the worker rejoins the LB half-open."""
    coord = Coordinator(_coord_cfg())
    spawned = []

    async def hook(worker_id, info):
        w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                      worker_id=worker_id))
        host, port = await w.start()
        spawned.append(w)
        return host, port

    coord.start_supervisor(hook)
    await coord.start()
    workers = {}
    try:
        for i in range(2):
            w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                          worker_id=f"w{i}"))
            host, port = await w.start()
            workers[f"w{i}"] = w
            coord.add_worker(f"w{i}", host, port)
        await coord.deploy_model(ModelConfig(name="m", architecture="fake"))
        out = await coord.submit("m", prompt=[1, 2, 3], max_new_tokens=3)
        assert out["tokens"] == [3, 2, 1]

        await workers.pop("w0").stop()          # hard kill, no drain
        assert await _wait_for(
            lambda: coord.get_stats()["supervisor_respawns"] >= 1), \
            "supervisor never respawned the killed worker"
        assert "w0" in coord.router.workers     # same id, fresh process
        assert spawned and "m" in spawned[-1].engines   # model reloaded
        st = coord.lb.workers["w0"]
        assert st.breaker_state != BREAKER_OPEN  # half-open (or re-closed)
        stats = coord.get_stats()
        assert stats["supervisor"]["degraded_workers"] == []
        # the rejoined fleet still serves, token-exact
        out = await coord.submit("m", prompt=[5, 6], max_new_tokens=2)
        assert out["tokens"] == [6, 5]
    finally:
        await coord.stop()
        for w in list(workers.values()) + spawned:
            try:
                await w.stop()
            except Exception:
                pass


@pytest.mark.chaos
async def test_supervisor_crashloop_breaker_opens():
    """A restart hook that cannot produce a live worker: after N failed
    attempts inside the window the breaker opens, the corpse leaves both
    planes with its shards FAILED, and the survivor keeps serving."""
    coord = Coordinator(_coord_cfg(supervisor_crashloop_threshold=2,
                                   supervisor_crashloop_window_s=30.0))
    attempts = []

    async def hook(worker_id, info):
        attempts.append(worker_id)
        raise RuntimeError("no capacity")

    coord.start_supervisor(hook)
    await coord.start()
    workers = {}
    try:
        for i in range(2):
            w = WorkerServer(ServerConfig(host="127.0.0.1", port=0,
                                          worker_id=f"w{i}"))
            host, port = await w.start()
            workers[f"w{i}"] = w
            coord.add_worker(f"w{i}", host, port)
        cfg = ModelConfig(name="m", architecture="fake")
        await coord.deploy_model(cfg)

        await workers.pop("w0").stop()
        assert await _wait_for(
            lambda: coord.get_stats()["supervisor_crashloop_opens"] >= 1), \
            "crash-loop breaker never opened"
        assert len(attempts) >= 2               # threshold attempts made
        stats = coord.get_stats()
        assert stats["supervisor_respawns"] == 0
        assert stats["supervisor"]["degraded_workers"] == ["w0"]
        assert "w0" not in coord.router.workers  # out of both planes
        shard_status = {s.worker_id: s.status
                        for s in coord.registry.all_shards("m", cfg.version)}
        assert shard_status["w0"] is ModelStatus.FAILED
        assert shard_status["w1"] is ModelStatus.READY
        # the survivor serves; no further respawn attempts are burned
        n_attempts = len(attempts)
        out = await coord.submit("m", prompt=[7, 8, 9], max_new_tokens=3)
        assert out["tokens"] == [9, 8, 7]
        await asyncio.sleep(0.3)
        assert len(attempts) == n_attempts      # degraded stays parked
        # operator re-arm clears the breaker
        assert coord.supervisor_reset("w0")
        assert coord.get_stats()["supervisor"]["degraded_workers"] == []
    finally:
        await coord.stop()
        for w in workers.values():
            try:
                await w.stop()
            except Exception:
                pass
