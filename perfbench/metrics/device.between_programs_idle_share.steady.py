"""Share of the traced slice in which no PROGRAM was running on the device: the
host's part of the idle time (the rest of ``device.idle_share`` is gaps
between ops inside programs).
"""

from perfbench.lib import readers

NAME = "device.between_programs_idle_share.steady"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.between_programs_pct(run)
