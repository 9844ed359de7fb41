"""Config tree + file loader tests (the config file the reference README
promised at ``README.md:39`` but never shipped)."""

import dataclasses
import json
import pathlib
import re

import pytest

from distributed_inference_engine_tpu.config import (
    Config,
    EngineConfig,
    MeshConfig,
    ModelConfig,
    config_from_dict,
    load_config,
)


def test_model_config_round_trip():
    mc = ModelConfig(name="llama3-8b", architecture="llama", max_seq_len=8192)
    d = mc.to_dict()
    mc2 = ModelConfig.from_dict(d)
    assert mc2 == mc


def test_from_dict_ignores_unknown_fields():
    mc = ModelConfig.from_dict({"name": "m", "totally_new_field": 1})
    assert mc.name == "m"


def test_mesh_config():
    m = MeshConfig(dp=2, tp=4)
    assert m.n_devices == 8
    assert m.axis_sizes() == {"dp": 2, "pp": 1, "sp": 1, "tp": 4, "ep": 1}


def test_config_from_dict_sections():
    cfg = config_from_dict(
        {
            "models": [{"name": "m", "architecture": "gpt2"}],
            "mesh": {"tp": 8},
            "batcher": {"max_batch_size": 16},
            "cache": {"policy": "lfu", "max_size": 99},
            "health": {"max_consecutive_failures": 5},
            "server": {"port": 9999},
        }
    )
    assert cfg.models[0].architecture == "gpt2"
    assert cfg.mesh.tp == 8 and cfg.mesh.dp == 1
    assert cfg.batcher.max_batch_size == 16
    assert cfg.cache.policy == "lfu"
    assert cfg.health.max_consecutive_failures == 5
    assert cfg.server.port == 9999


def test_load_json_and_yaml_and_toml(tmp_path):
    data = {"mesh": {"tp": 2, "dp": 4}, "models": [{"name": "x"}]}
    jp = tmp_path / "c.json"
    jp.write_text(json.dumps(data))
    cfg = load_config(str(jp))
    assert cfg.mesh.tp == 2 and cfg.mesh.n_devices == 8
    assert cfg.models[0].name == "x"

    yp = tmp_path / "c.yaml"
    yp.write_text("mesh:\n  tp: 4\nengine:\n  max_slots: 32\n")
    cfg = load_config(str(yp))
    assert cfg.mesh.tp == 4 and cfg.engine.max_slots == 32

    tp = tmp_path / "c.toml"
    tp.write_text("[mesh]\ntp = 8\n\n[batcher]\nmax_latency_ms = 5.0\n")
    cfg = load_config(str(tp))
    assert cfg.mesh.tp == 8 and cfg.batcher.max_latency_ms == 5.0


def test_default_config_is_valid():
    cfg = Config()
    d = cfg.to_dict()
    assert "engine" in d and "mesh" in d


def test_multihost_config_section(tmp_path):
    """The multihost section round-trips through the config-file loader
    (pod-slice deployments drive workers from files, not flags)."""
    import json

    from distributed_inference_engine_tpu.config import load_config

    p = tmp_path / "w.json"
    p.write_text(json.dumps({
        "server": {"worker_id": "h0", "port": 9000},
        "multihost": {"enabled": True,
                      "coordinator_address": "10.0.0.1:8476",
                      "num_processes": 4, "process_id": 2},
    }))
    cfg = load_config(str(p))
    assert cfg.multihost.enabled is True
    assert cfg.multihost.coordinator_address == "10.0.0.1:8476"
    assert cfg.multihost.num_processes == 4
    assert cfg.multihost.process_id == 2
    # defaults when absent
    p2 = tmp_path / "w2.json"
    p2.write_text(json.dumps({"server": {"worker_id": "h1"}}))
    assert load_config(str(p2)).multihost.enabled is False


# ---------------------------------------- retired metadata, and live fields


_RETIRED_KEYS = ["decode_mode", "mixed_step_tokens", "spec_async",
                 "spec_draft_model", "spec_max_draft", "spec_bubble_floor_s"]
_RETIRED_IMPLS = ["pallas", "pallas_interpret", "pallas-decode-fw",
                  "pallas-decode-fw_interpret", "pallas-ragged",
                  "pallas-ragged_interpret"]


@pytest.mark.parametrize("arch", ["llama", "fake"])
@pytest.mark.parametrize("key", _RETIRED_KEYS)
def test_retired_key_raises_at_load(key, arch):
    """A deploy that still carries a key PR 29 removed fails at load, by
    the key's name, before any weight exists — never silently ignored."""
    from distributed_inference_engine_tpu.models import engine_from_config

    cfg = ModelConfig(name="m", architecture=arch, metadata={
        "size": "llama-tiny", "continuous": 1, key: 1})
    with pytest.raises(ValueError, match=rf"'{key}' is retired"):
        engine_from_config(cfg)


@pytest.mark.parametrize("impl", _RETIRED_IMPLS)
def test_retired_attention_string_raises(impl):
    from distributed_inference_engine_tpu.engine.continuous import (
        resolve_decode_body)
    from distributed_inference_engine_tpu.models import (
        engine_from_config, llama_spec)

    cfg = ModelConfig(name="m", architecture="llama", metadata={
        "size": "llama-tiny", "continuous": 1, "attention_impl": impl})
    with pytest.raises(ValueError, match=rf"'{impl}' is retired"):
        engine_from_config(cfg)
    # an engine built directly refuses the string too
    with pytest.raises(ValueError, match="attention_impl"):
        resolve_decode_body(impl, "tpu", llama_spec("llama-tiny"))


def _package_sources():
    root = pathlib.Path(__file__).resolve().parents[1] / (
        "distributed_inference_engine_tpu")
    return {p: p.read_text() for p in root.rglob("*.py")
            if p.name != "config.py"}


@pytest.mark.parametrize(
    "field", [f.name for f in dataclasses.fields(EngineConfig)])
def test_engine_field_is_read(field):
    """Every ``EngineConfig`` field is read by some module of the package
    other than ``config.py``: as an attribute (``cfg.<field>``), or by name
    (``getattr(cfg, "<field>", ...)``, the metadata loop's key tuple)."""
    read = re.compile(rf"\.{field}\b(?!\s*=[^=])|[\"']{field}[\"']")
    assert any(read.search(src) for src in _package_sources().values()), (
        f"EngineConfig.{field} is read by nothing in the package")


def test_engine_config_has_eighteen_fields():
    """PR 43 retired ``defer_sync`` and ``defer_admission``: the engine has
    one dispatch-and-harvest sequence and no field selects another."""
    names = {f.name for f in dataclasses.fields(EngineConfig)}
    assert len(names) == 18
    assert not names & {"defer_sync", "defer_admission"}
