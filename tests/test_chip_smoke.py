"""chip_smoke.py on the CPU: its tiny debug mode passes and says
platform=cpu; every way of failing ends in a non-zero exit and NO result
line — the case to pin is a failure that is caught and still exits 0."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _result_lines(text: str):
    return [ln for ln in text.splitlines() if ln.startswith('{"ok"')]


def _run_tiny(monkeypatch, capsys, tmp_path, legs="server"):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    rc = chip_smoke.main(["--preset", "tiny", "--legs", legs])
    return rc, capsys.readouterr().out


def test_default_preset_fails_without_a_tpu(monkeypatch, capsys, tmp_path):
    """No accelerator here: the worker child is started for platform=tpu,
    dies at its first jax call, and the script exits non-zero with no
    result line. (The suite's JAX_PLATFORMS=cpu must not leak into it.)"""
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    rc = chip_smoke.main(["--legs", "server"])
    out = capsys.readouterr().out
    assert rc != 0
    assert "chip_smoke FAILED" in out and "w0 exited" in out
    assert not _result_lines(out)


def test_tiny_preset_refuses_to_run_off_cpu(monkeypatch, capsys, tmp_path):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    assert chip_smoke.main(["--preset", "tiny"]) != 0
    assert not _result_lines(capsys.readouterr().out)


@pytest.mark.slow
def test_tiny_mode_passes_and_the_parent_never_imports_jax(tmp_path):
    """End to end in a fresh interpreter: worker + coordinator + client +
    restart on the CPU; the last stdout line is the result with
    platform=cpu; the parent process has not imported jax."""
    code = ("import sys, chip_smoke; "
            f"chip_smoke.WORK = {str(tmp_path)!r}; "
            "rc = chip_smoke.main(['--preset', 'tiny']); "
            "assert 'jax' not in sys.modules, 'parent imported jax'; "
            "sys.exit(rc)")
    # without the suite's 8 virtual devices: one visible device, so the
    # multichip legs are skipped as on a one-chip machine
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600, env=dict(env, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"
    assert "platform=cpu" in out.stdout
    assert "multichip legs skipped: 1 device(s)" in out.stdout


def test_a_failing_worker_child_fails_the_smoke(monkeypatch, capsys,
                                                tmp_path):
    """Bad model spec: the worker exits non-zero before it listens."""
    monkeypatch.setitem(chip_smoke.PRESETS["tiny"], "size", "no-such-size")
    rc, out = _run_tiny(monkeypatch, capsys, tmp_path)
    assert rc != 0
    assert "w0 exited 1" in out and "no-such-size" in out
    assert not _result_lines(out)


@pytest.mark.slow
def test_a_failing_request_fails_the_smoke(monkeypatch, capsys, tmp_path):
    """One of the concurrent requests is rejected by the coordinator (an
    empty prompt): the RPC error must surface as a non-zero exit, not as a
    printed ``error:`` followed by success."""
    real = chip_smoke.make_prompts

    def with_an_empty_prompt(preset, n, seed):
        prompts = real(preset, n, seed)
        prompts[-1] = []
        return prompts

    monkeypatch.setattr(chip_smoke, "make_prompts", with_an_empty_prompt)
    rc, out = _run_tiny(monkeypatch, capsys, tmp_path)
    assert rc != 0
    assert "empty prompt" in out
    assert not _result_lines(out)


@pytest.mark.slow
def test_a_failing_check_fails_the_smoke(monkeypatch, capsys, tmp_path):
    """A token id at or past the vocab size is a failed output check."""
    monkeypatch.setitem(chip_smoke.PRESETS["tiny"], "vocab_size", 2)
    rc, out = _run_tiny(monkeypatch, capsys, tmp_path)
    assert rc != 0
    assert "token ids outside the vocab" in out
    assert not _result_lines(out)
