"""Every configuration file (cells and rehearsals) against the seam of
``lib/families.py``: its family resolves to counts and a reference, the
count of parameter bytes is the tree's, the ``serve`` block reaches the
worker whole, and a family's served chains pass its own reference and fail
the dense decoder's."""

import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import families, procs, session  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "configs"))
                 if f.endswith(".json"))
ON_CPU = [n for n in CONFIGS
          if session.load_config(n)["platform"] == "cpu"]
OTHER_FAMILY = [n for n in ON_CPU if families.family_name(
    session.load_config(n)) != families.DEFAULT]


def program_spec(cfg):
    from distributed_inference_engine_tpu.models import spec_for_architecture

    serve = cfg["serve"]
    return spec_for_architecture(serve["architecture"], size=serve["size"],
                                 max_seq_len=serve["max_seq_len"])


def test_every_configuration_is_found():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        cells += json.load(f)["workloads"]
    assert {w["config"] for w in cells} == set(CONFIGS)


@pytest.mark.parametrize("name", CONFIGS)
def test_family_resolves_to_counts_and_a_reference(name):
    cfg = session.load_config(name)
    counts, ref = families.counts(cfg), families.reference(cfg)
    assert counts.param_bytes(cfg) > 0 and counts.kv_bytes_per_token(cfg) > 0
    assert isinstance(counts.CACHE, str) and counts.CACHE
    mats = counts.weight_matmuls(cfg)
    assert mats and all(len(m) == 5 and m[1] > 0 and m[2] > 0 and m[3] > 0
                        for m in mats)
    assert callable(ref.logits) and callable(ref.build_params)
    spec = program_spec(cfg)
    for key, field in ref.SPEC_PAIRS:
        assert cfg[key] == getattr(spec, field), key
    # the int4 questions are asked of a family that stores int4 and of no
    # other
    stores_int4 = any(m[4] == "int4" for m in mats)
    assert bool(families.int4_calls_per_pass(cfg)) == stores_int4
    assert stores_int4 == (cfg["serve"].get("weight_bits") == 4)


def test_a_family_without_its_files_is_an_error():
    with pytest.raises(FileNotFoundError):
        families.counts({"family": "no_such_family"})
    with pytest.raises(FileNotFoundError):
        families.reference({"family": "no_such_family"})


@pytest.mark.parametrize("name", CONFIGS)
def test_param_bytes_are_the_bytes_of_the_tree(name):
    """The family's count against the tree its reference rebuilds (shapes
    only where the configuration is a chip's size)."""
    import jax

    cfg = session.load_config(name)
    ref = families.reference(cfg)
    spec = program_spec(cfg)
    if cfg["platform"] == "cpu":
        tree = ref.build_params(cfg, spec, 7)
    else:
        tree = jax.eval_shape(lambda: ref.build_params(cfg, spec, 7))
    have = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))
    want = families.counts(cfg).param_bytes(cfg)
    # check_device's tolerance; the dense int4 head is stored padded
    assert abs(have - want) <= 0.001 * want, (have, want)


@pytest.mark.parametrize("name", CONFIGS)
def test_serve_reaches_the_worker_and_the_coordinator_whole(name):
    from distributed_inference_engine_tpu.cli.worker import parse_model_arg
    from distributed_inference_engine_tpu.cluster.worker import (
        _model_identity,
    )
    from distributed_inference_engine_tpu.config import ModelConfig

    serve = session.load_config(name)["serve"]
    model = procs.model_dict(serve, 5)
    assert model["quantized"] == serve.get("quantized",
                                           "weight_bits" in serve)
    assert ("weight_bits" in model["metadata"]) == ("weight_bits" in serve)
    assert model.get("dtype") == serve.get("dtype")
    for key, val in (serve.get("metadata") or {}).items():
        assert model["metadata"][key] == val
    for key in ("page_size", "num_pages", "prefill_buckets"):
        assert model["metadata"][key] == serve[key]
    assert model["metadata"]["seed"] == 5
    deployed = parse_model_arg(procs.deploy_spec(serve))
    assert _model_identity(deployed) == _model_identity(
        ModelConfig.from_dict(model))
    for key, val in (serve.get("metadata") or {}).items():
        if not isinstance(val, (list, dict)):
            assert str(deployed.metadata[key]) == str(val)


def test_serve_metadata_wins_and_bad_values_are_refused():
    serve = dict(session.load_config("tiny")["serve"],
                 metadata={"num_pages": 80, "kept_experts": [0, 3]})
    meta = procs.model_dict(serve, 1)["metadata"]
    assert meta["num_pages"] == 80 and meta["kept_experts"] == [0, 3]
    assert "kept_experts" not in procs.deploy_spec(serve)
    with pytest.raises(procs.BenchFailure):
        procs.deploy_spec(dict(serve, metadata={"note": "a,b"}))


def served_chains(cfg, n_chains=2, n_prompt=48, n_out=24):
    """Greedy chains from the program's ContinuousEngine, built the way the
    worker builds it for this ``serve`` block (random init, no checkpoint)."""
    import random

    from distributed_inference_engine_tpu.config import ModelConfig
    from distributed_inference_engine_tpu.engine.types import (
        GenerationRequest,
    )
    from distributed_inference_engine_tpu.models import engine_from_config

    model = procs.model_dict(cfg["serve"], 7)
    model["metadata"]["warmup"] = 0
    engine = engine_from_config(ModelConfig.from_dict(model))
    rng = random.Random(11)
    cases = []
    for i in range(n_chains):
        prompt = [rng.randrange(1, cfg["vocab_size"])
                  for _ in range(n_prompt)]
        (res,) = engine.generate([GenerationRequest(
            prompt=prompt, max_new_tokens=n_out, temperature=0.0,
            eos_id=-1)])
        cases.append({"label": f"chain-{i}", "prompt": prompt,
                      "tokens": [int(t) for t in res.tokens]})
    return cases


def run_check(tmp_path, cfg, cases, tag):
    job = tmp_path / f"job-{tag}.json"
    job.write_text(json.dumps({"config": cfg, "weight_seed": 7,
                               "cases": cases}))
    # the CPU jaxlib misreads its own persistent cache (tests/conftest.py)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "reference", "check.py"),
         str(job)], env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=300)
    return proc.returncode, proc.stdout


@pytest.fixture(scope="module")
def chains():
    made = {}

    def get(name):
        if name not in made:
            made[name] = served_chains(session.load_config(name))
        return made[name]
    return get


@pytest.mark.parametrize("name", ON_CPU)
def test_served_chains_pass_their_own_reference(name, chains, tmp_path):
    cfg = session.load_config(name)
    rc, out = run_check(tmp_path, cfg, chains(name), "own")
    assert rc == 0, out
    assert f"family={families.family_name(cfg)}" in out
    # a token altered where it is produced is seen
    bad = [dict(c, tokens=[(t + 1) % cfg["vocab_size"] for t in c["tokens"]])
           for c in chains(name)]
    rc, out = run_check(tmp_path, cfg, bad, "altered")
    assert rc != 0, out


@pytest.mark.parametrize("name", OTHER_FAMILY)
def test_another_familys_chains_fail_the_dense_reference(name, chains,
                                                         tmp_path):
    """The configuration pointed at the dense decoder (its ``family`` key
    taken out): the same chains are refused."""
    cfg = session.load_config(name)
    dense = {k: v for k, v in cfg.items() if k != "family"}
    dense["serve"] = dict(cfg["serve"], weight_bits=4)
    rc, out = run_check(tmp_path, dense, chains(name), "dense")
    assert rc != 0, out
    assert "family=dense_int4" in out
    # and not for its weights alone: the dense decoder cannot compute this
    # family's own tree (it raises, or its logits refuse the chain)
    import jax.numpy as jnp
    import numpy as np

    from perfbench.reference import check, decoder

    tree = families.reference(cfg).build_params(cfg, program_spec(cfg), 7)
    case = chains(name)[0]
    seq = jnp.asarray(case["prompt"] + case["tokens"], jnp.int32)
    try:
        lg = np.asarray(decoder.logits(cfg, tree, seq))
    except (TypeError, ValueError, KeyError):
        return
    assert not check.judge(lg, len(case["prompt"]), case["tokens"])["ok"]
