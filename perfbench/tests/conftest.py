"""What every made run of these tests finds in its work directory beside
the reductions its own file lays there: the slice account of a worker's
trace (``lib/slicereaders.py``), as a child process would have left it."""

import json

import pytest

# the made slice: 4 s between the anchors inside 5 s of trace, the device
# idle for 0.5 s of them (0.1 inside programs), the engine thread under
# spans for 3.9 s, 2.4 of them waits
MADE_SLICE = {
    "found": True, "devices": 1, "window_s": 4.0, "tracered_window_s": 5.0,
    "busy_s": 3.5, "idle_s": 0.5, "idle_in_programs_s": 0.1,
    "idle_between_programs_s": 0.4, "device_outside_window_s": 0.01,
    "self_s": 3.5, "scoped_self_s": 3.3, "loop_self_s": 0.01,
    "engine_spans_s": 3.9, "engine_wait_s": 2.4, "engine_work_s": 1.5,
    "gaps_s": 0.45, "gaps_under_work_s": 0.25, "gaps_under_wait_s": 0.18,
    "decode_steps_by_counters": 400.0, "decode_steps_by_sample_op": 404}


@pytest.fixture(autouse=True)
def made_slice_account(tmp_path):
    (tmp_path / "slice-w0.json").write_text(json.dumps(MADE_SLICE))
