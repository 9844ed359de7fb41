"""The dense int4 family's made run (both Mistral cells), and what its own
readers take from it."""

import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import session  # noqa: E402
from perfbench.lib.session import RunData  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def made_run(tmp_path):
    """A traced run of a Mistral cell: 9 decode programs of 16 steps in the
    slice, the int4 kernel 0.8 s of the device's 3 busy seconds."""
    trace_dir = tmp_path / "trace-w0"
    trace_dir.mkdir()
    return RunData(
        config=session.load_config("mistral-7b-int4"), mix={}, records=[],
        t_open=0.0, t_close=51.0, setup={}, device={"kind": "TPU v5 lite"},
        workers_before={"w0": {}}, workers_after={"w0": {}},
        trace_dirs={"w0": str(trace_dir)},
        trace={"program_s": {"decode": 1.0}, "program_calls": {"decode": 9},
               "decode_steps": 144.0, "int4_kernel_s": {"decode": 0.8},
               "classes": {"int4_matmul": 0.8}, "busy_s": 3.0,
               "window_s": 4.0, "between_programs_s": 0.2})


@pytest.mark.parametrize("suffix", ["steady", "overload"])
def test_the_readers_on_a_made_run(suffix, tmp_path):
    run = made_run(tmp_path)
    assert reader(f"model.decode_step_ms.{suffix}")(run) == \
        pytest.approx(1e3 / 144)
    assert reader(f"int4_matmul_time_share.{suffix}")(run) == \
        pytest.approx(100 * 0.8 / 3.0)
    assert 0 < reader(f"int4_matmul_roofline.{suffix}")(run)
    assert reader(f"device.idle_share.{suffix}")(run) == pytest.approx(25.0)
    assert reader(f"device.between_programs_idle_share.{suffix}")(run) == \
        pytest.approx(5.0)
