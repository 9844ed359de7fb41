"""What the set-up's four phases leave of ``setup_s``: process starts, imports,
the backend's start, the coordinator's start and connect. The worker's ``boot``
marks are there to explain it (``lib/setupreaders.py``).
"""


NAME = "setup.unplaced_s"
LAYER = "set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    s = run.setup
    keys = ("setup_s", "load_s", "warmup_s", "prime_s", "ramp_s")
    if any(s.get(k) is None for k in keys):
        return None
    return s["setup_s"] - sum(s[k] for k in keys[1:])
