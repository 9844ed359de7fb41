"""BENCHMARK.json against the files it names: the harness finds everything
by name, so a name without its file (or a file that disagrees) must fail
here and not on the chip."""

import importlib.util
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import session, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(metric, man):
    return set(metric.get("workloads")
               or [w["name"] for w in man["workloads"]])


def test_names_and_files():
    man = manifest()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in man[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in man["configs"]:
        cfg = session.load_config(c["name"])
        assert c["file"] == f"perfbench/configs/{c['name']}.json"
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}
    for w in man["workloads"]:
        mix = traffic.load_mix(w["traffic"])
        assert mix["rate_rps"] > 0 and len(w["why"]) <= 200
        assert w["chips"] == 1


def test_every_per_layer_metric_has_its_reader_and_they_agree():
    man = manifest()
    for m in man["per_layer"]:
        path = os.path.join(ROOT, "perfbench", "metrics", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert (mod.NAME, mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE,
                mod.MOVES) == (m["name"], m["layer"], m["unit"], m["better"],
                               m["source"], m["moves"])
        assert callable(mod.read)


def test_a_metric_moves_something_its_cells_report():
    man = manifest()
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        assert cells_of(m, man) <= cells_of(e2e[m["moves"]], man)
    for w in man["workloads"]:
        mine = [m for m in man["end_to_end"] if w["name"] in cells_of(m, man)]
        assert len(mine) >= 2                    # setup_s and one other
        assert any(w["name"] in cells_of(m, man) for m in man["per_layer"])
