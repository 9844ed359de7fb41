"""BENCHMARK.json against the files it names: the harness finds everything
by name, so a name without its file (or a file that disagrees) must fail
here and not on the chip."""

import importlib.util
import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run as bench  # noqa: E402
from perfbench.lib import families, procs, session, traffic  # noqa: E402
from perfbench.lib.loadgen import Record  # noqa: E402
from perfbench.lib.traffic import Request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# the layers every overload cell shares: ONE entry and ONE file each, named
# ``<reader>.overload``, listing every cell that reports ``out_tok_s``. A
# cell brings a configuration, a mix and its own mechanism's metrics only
SHARED = [
    "loadgen.lateness_p99_ms", "client.tpot_p50_ms", "client.in_flight_mean",
    "coord.pool_wait_p50_ms", "coord.pool_waiting_mean",
    "coord.stream_frames_per_s", "coord.streams_in_flight_mean",
    "pump.in_flight_mean", "pump.inbox_wait_p50_ms", "worker.shed",
    "engine.occupancy", "engine.host_busy_share", "kv.pool_used_share",
    "kv.copy_time_share", "device.idle_share",
    "device.between_programs_idle_share"]
FOLDED = re.compile(r'scope_reading\(run, "(\w+)"(?:,\s*(.*?))?\)\s*$',
                    re.S | re.M)


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


@pytest.mark.parametrize("what, most", [
    ("configs", 24), ("workloads", 24), ("per_layer", 128), ("bytes", 65536)])
def test_the_manifest_is_within_what_a_check_takes(what, most):
    """The driver refuses BENCHMARK.json before any run when a list or the
    file outgrows these (PR 33 was refused at 130 per-layer entries)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    size = len(text.encode()) if what == "bytes" else len(json.loads(text)[what])
    assert 1 <= size <= most


@pytest.mark.parametrize("name", SHARED)
def test_a_shared_layers_reader_has_one_entry_and_one_file(name):
    """``<reader>.overload`` lists exactly the cells of ``out_tok_s``, and
    no entry (so no file: ``test_one_file_for_each_entry``) carries the
    reader under a cell's own suffix; only the steady cell's, which moves
    ``tpot_p50_ms``, stands beside it."""
    man = manifest()
    (tok_s,) = [m for m in man["end_to_end"] if m["name"] == "out_tok_s"]
    (entry,) = [m for m in man["per_layer"]
                if m["name"] == f"{name}.overload"]
    assert entry["workloads"] == tok_s["workloads"]
    assert entry["moves"] == "out_tok_s"
    listed = {m["name"] for m in man["per_layer"]
              if m["name"] == name or m["name"].startswith(name + ".")}
    assert listed <= {name, f"{name}.overload", f"{name}.steady"}


def folded_readers(man):
    """(entry, function, scope names or None) of every reader that asks the
    run's own family for its reading (``lib/families.py``
    ``scope_reading``)."""
    out = []
    for m in man["per_layer"]:
        path = os.path.join(ROOT, "perfbench", "metrics", f"{m['name']}.py")
        with open(path) as f:
            found = FOLDED.search(f.read())
        if found:
            names = eval(found.group(2)) if found.group(2) else None
            out.append((m, found.group(1),
                        (names,) if isinstance(names, str) else names))
    return out


def test_a_folded_reader_lists_the_cells_whose_family_has_its_scopes():
    """One reader a reading (PR 49): an entry whose reader asks the run's
    family for ``fn`` lists exactly the ``out_tok_s`` cells whose family's
    module (``counts/<family>.py`` ``SCOPE_READERS``) has ``fn`` and, for a
    share of the device's time, names the scopes among its own. A cell a
    later PR appends is listed under every reading its family has, and
    under no other."""
    man = manifest()
    (tok_s,) = [m for m in man["end_to_end"] if m["name"] == "out_tok_s"]
    cfg_of = {w["name"]: session.load_config(w["config"])
              for w in man["workloads"]}
    folded = folded_readers(man)
    assert len(folded) >= 15
    for m, fn, names in folded:
        assert m["name"].endswith(".overload") and m["moves"] == "out_tok_s"
        have = []
        for cell in tok_s["workloads"]:
            mod = families.scopes(cfg_of[cell])
            own = getattr(mod, "SHARE_SCOPES", getattr(mod, "SCOPES", ()))
            if hasattr(mod, fn) and (fn != "share_pct"
                                     or all(n in own for n in names)):
                have.append(cell)
        assert m["workloads"] == have, (m["name"], fn, names)


def test_no_reading_is_kept_under_two_names():
    """A reading several families have stands under ONE name: no entry is
    another's name with a cell's suffix in place of ``.overload``."""
    names = {m["name"] for m in manifest()["per_layer"]}
    for n in names:
        base, _, suffix = n.rpartition(".")
        if suffix not in ("overload", "steady") and base:
            assert f"{base}.overload" not in names, n


def test_one_file_for_each_entry():
    files = sorted(f[:-3] for f in os.listdir(
        os.path.join(ROOT, "perfbench", "metrics")) if f.endswith(".py"))
    assert files == sorted(m["name"] for m in manifest()["per_layer"])


# ------------------------------------------ every listed reader on a made run


def made_run_of(family):
    """``made_run(tmp_path)`` of ``tests/test_<family>.py``: the traced run
    a family's own test file builds for its readers. ``None`` for a family
    that brings none (its cells are then skipped here, not failed)."""
    path = os.path.join(HERE, f"test_{family}.py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location("made_" + family, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, "made_run", None)


def merge(into, more):
    for k, v in more.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            merge(into[k], v)
        else:
            into[k] = v
    return into


def with_the_shared_layers(run, tmp_path):
    """What the shared layers' readers take, laid over a family's made run:
    three requests with the program's marks, two 2 Hz samples of the worker
    and the coordinator, the counters of both, the set-up's split and the
    host-span reduction of the traced slice."""
    marks = {"received": 0.0, "dispatched": 0.002, "conn_acquired": 0.012,
             "worker.received": 0.012, "worker.submitted": 0.052,
             "worker.admitted": 0.056, "worker.first_token": 0.176,
             "worker.first_frame_sent": 0.179, "first_frame": 0.180}
    run.records = [
        Record(req=Request(i, "window", 1.0 + i, [1, 2, 3], 3), due=1.0 + i,
               sent=1.001 + i, frames=[(1.2 + i, 1), (1.3 + i, 2)],
               done=1.4 + i, tokens=[5, 6, 7], trace=dict(marks))
        for i in range(3)]
    # (only the shared layers' readers read the samples)
    run.samples = [
        {"t": t, "workers": {"w0": {
            "pumps": {procs.MODEL: {"in_flight": 8}},
            "models": {procs.MODEL: {"live_slots": 7,
                                     "kv": {"utilization": 0.3}}}}},
         "coord": {"pool_waiting": 4, "streams_in_flight": 12}}
        for t in (1.25, 1.75)]
    for side, n in ((run.workers_before, 0), (run.workers_after, 1)):
        merge(side["w0"], {
            "overloaded_count": 0, "deadline_expired_count": 0,
            "error_count": 0, "device": {
                "compile": {"backend_compiles": 40,
                            "backend_compile_s": 31.5 + n},
                "memory": {"0": {"bytes_in_use": 11 * 10 ** 9}}}})
    run.coord_before, run.coord_after = \
        {"stream_frames": 100}, {"stream_frames": 5200}
    run.setup = {"load_s": 20.0, "warmup_s": 30.0, "prime_s": 1.0,
                 "ramp_s": 10.0, "setup_s": 63.0}
    run.device["memory_peak_bytes"] = 12 * 10 ** 9
    (tmp_path / "hostspans-w0.json").write_text(json.dumps({
        "engine_thread_found": True, "window_s": 4.0, "engine_busy_s": 1.0,
        "idle_gap_s": 0.5, "idle_attributed_s": 0.45, "device_busy_s": 3.0,
        "kv_copy_s": 0.3}))
    return run


@pytest.mark.parametrize("cell", [w["name"] for w in manifest()["workloads"]])
def test_every_listed_reader_returns_a_value_on_a_made_run(cell, tmp_path):
    """Each cell's whole ``--trace 1`` line: every entry that lists the
    cell (and the entries every cell reports) has its reader find something
    to read where the made run of the cell's family
    (``test_<family>.py`` ``made_run``) carries the shared layers too.
    Nothing here names a cell, a family or a count: a cell a later PR
    appends is found by its configuration's ``family``."""
    man = manifest()
    config = bench.find_cell(man, cell)["config"]
    family = families.family_name(session.load_config(config))
    made = made_run_of(family)
    if made is None:
        pytest.skip(f"tests/test_{family}.py brings no made_run")
    run = with_the_shared_layers(made(tmp_path), tmp_path)
    listed = bench.metric_names(man, "per_layer",
                                dict(bench.find_cell(man, cell)))
    assert listed
    for m in listed:
        value = bench.load_reader(m["name"]).read(run)
        assert value is not None and math.isfinite(value), m["name"]
        # (the family runs' made seconds and bytes give no true roofline)
        if m["unit"] == "%" and m["name"].rsplit(".", 1)[0] in SHARED:
            assert 0.0 <= value <= 100.0, (m["name"], value)
