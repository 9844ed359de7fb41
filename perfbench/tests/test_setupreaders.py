"""The readers of the program's own account of its set-up (``lib/
setupreaders.py``), the two set-up metrics of PR 37 and ``tools/
setup_split.py``'s row, on made values of this file's own."""

import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import procs, setupreaders  # noqa: E402
from perfbench.lib.session import RunData  # noqa: E402
from perfbench.tools import setup_split  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def entry(t0, program, span, cache="hit", compile_s=0.004):
    return {"program": program, "t0": t0, "trace_s": 0.01, "lower_s": 0.02,
            "compile_s": compile_s, "cache": cache, "span": span}


def worker(mono, count, seconds, last, backend_compile_s):
    """One ``metrics`` reply of a worker that loaded in 50 s: 18.5 s build,
    a warm-up of two rounds that took 31 s of the 31.5 s the worker timed."""
    warm = {"wall_s": 31.0, "trace_s": 6.0, "lower_s": 9.0, "compile_s": 12.0,
            "cache_retrieval_s": 11.0, "run_s": 4.0, "cache_hits": 40,
            "cache_misses": 0, "rounds": [{"batch": 1, "bucket": 256},
                                          {"batch": 1, "bucket": 512}]}
    return {
        "mono": mono,
        "boot": {"main_entered": 0.25, "jax_imported": 2.0, "load_begin": 2.5,
                 "load_end": 52.5, "listening": 52.75},
        "model_setup": {procs.MODEL: {"load_s": 50.0, "warmup_s": 31.5,
                                      "warmup": warm}},
        "device": {"compile": {"backend_compiles": 40 + count,
                               "backend_compile_s": backend_compile_s}},
        "models": {procs.MODEL: {"compiles_after_warmup": {
            "count": count, "seconds": seconds, "last": last}}}}


def made_run():
    prime = [entry(990.0, "jit(_decode_chunk)", "engine.decode.dispatch",
                   "miss", 2.0)]
    inside = [entry(1010.0, "jit(_install_first)", "engine.admit"),
              entry(1030.0, "jit(_where)", "engine.set_active", "unstored")]
    return RunData(
        config={}, mix={}, records=[], t_open=70.0, t_close=121.0,
        setup={"workers_ready_s": 53.5, "load_s": 18.5, "warmup_s": 31.5,
               "prime_begin_s": 56.5, "prime_s": 3.25, "ramp_s": 10.0,
               "setup_s": 70.0},
        device={"kind": "TPU v5 lite"},
        workers_before={"w0": worker(1000.0, 1, 2.03, prime, 14.0)},
        workers_after={"w0": worker(1051.0, 3, 2.098, prime + inside, 14.008)})


def test_the_two_metrics_of_the_manifest():
    run = made_run()
    assert reader("setup.compile_in_window_s")(run) == pytest.approx(0.008)
    assert reader("setup.unplaced_s")(run) == pytest.approx(
        70.0 - 18.5 - 31.5 - 3.25 - 10.0)
    del run.setup["prime_s"]
    assert reader("setup.unplaced_s")(run) is None


def test_the_readers_of_the_workers_own_split():
    run = made_run()
    assert setupreaders.boot_s(run) == pytest.approx(2.75)
    assert setupreaders.build_s(run) == 18.5
    assert [setupreaders.warmup_part_s(run, p)
            for p in setupreaders.WARMUP_PARTS] == [6.0, 9.0, 12.0, 11.0,
                                                    4.0, 31.0]
    with pytest.raises(ValueError):
        setupreaders.warmup_part_s(run, "sleep_s")
    assert setupreaders.prime_compile_s(run) == 2.03
    assert setupreaders.compiles_in_window(run) == 2
    inside = setupreaders.compiles_after_warmup(run)
    assert [(e["program"], e["span"], e["cache"], e["worker"])
            for e in inside] == [
        ("jit(_install_first)", "engine.admit", "hit", "w0"),
        ("jit(_where)", "engine.set_active", "unstored", "w0")]


@pytest.mark.parametrize("read, value", [
    (setupreaders.boot_s, None), (setupreaders.build_s, 19.0),
    (lambda run: setupreaders.warmup_part_s(run, "run_s"), None),
    (setupreaders.prime_compile_s, None),
    (setupreaders.compiles_in_window, None),
    (setupreaders.compiles_after_warmup, None)])
def test_a_program_without_the_keys_reads_none(read, value):
    """The parent commit's ``metrics`` reply: ``load_s`` and ``warmup_s``
    alone (their difference is there to read), no ``boot``, no ``mono``,
    no ``compiles_after_warmup``."""
    run = made_run()
    for side in (run.workers_before, run.workers_after):
        side["w0"] = {"model_setup": {procs.MODEL: {"load_s": 50.0,
                                                    "warmup_s": 31.0}},
                      "models": {procs.MODEL: {}}}
    assert read(run) == value


def test_the_tools_row_names_every_second_of_the_setup():
    run = made_run()
    saved = {"cell": "made", "seed": 7, "setup": run.setup,
             "program": setup_split.program_side(run)}
    saved["program"]["prime_compile_s"] = 2.03
    row = setup_split.split(saved)
    assert row == pytest.approx({
        "spawn": 0.75, "boot": 2.75, "build": 18.5,
        "trace": 6.0, "lower": 9.0, "compile": 12.0, "cache": 11.0,
        "run": 4.0, "install": 0.5, "connect": 3.0, "prime": 3.25,
        "compiling": 2.03, "ramp": 10.25, "unplaced": 0.0})
    text = setup_split.table([saved])
    assert text.splitlines()[2].startswith("| made | 7 | 70.0 | 0.75 | 2.75")
    assert text.splitlines()[2].endswith("| 100.0 |")
    # a program that reports none of it: the benchmark's own clock is
    # left, the rest is unplaced
    saved["program"] = setup_split.program_side(RunData(
        config={}, mix={}, records=[], t_open=0.0, t_close=1.0, setup={},
        device={}, workers_before={"w0": {}}, workers_after={"w0": {}}))
    bare = setup_split.split(saved)
    assert bare["build"] is None and bare["install"] is None
    assert bare["unplaced"] == pytest.approx(70.0 - 3.0 - 3.25 - 10.25)


def test_primes_compiles_are_read_from_a_copy_and_the_run_keeps_its_open():
    """``one_run`` reads what compiled under prime from the snapshot taken
    as prime ended; the per-layer readers, which run after it on the same
    run, still see ``workers_before`` as the window opened (ramp inside)."""
    run = made_run()
    at_open = run.workers_before
    first = entry(985.0, "jit(_decode_chunk)", "engine.decode.dispatch",
                  "miss", 1.5)
    primed = {"w0": worker(992.0, 1, 1.53, [first], 13.5)}
    ring = [{"name": "engine.decode.dispatch", "t": 1.0, "dur": 1.6,
             "args": {"compile": True, "programs": ["jit(_decode_chunk)"],
                      "cache": ["miss"], "compile_s": 1.5, "steps": 8}},
            {"name": "engine.step", "t": 0.9, "dur": 1.8, "args": {}}]
    side = setup_split.primed_side(run, primed, {"w0": ring})
    assert side["prime_compile_s"] == 1.53
    assert side["after_warmup_at_primed"]["w0"]["count"] == 1
    assert side["compiled_spans"] == {"w0": [{
        "name": "engine.decode.dispatch", "t": 1.0, "dur": 1.6,
        "programs": ["jit(_decode_chunk)"], "cache": ["miss"],
        "compile_s": 1.5}]}
    assert run.workers_before is at_open
    assert setupreaders.prime_compile_s(run) == 2.03
    assert reader("setup.compile_in_window_s")(run) == pytest.approx(0.008)
