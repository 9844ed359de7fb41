"""Share of the traced slice in which no PROGRAM was running on the device: the
host's part of the idle time.
"""

from perfbench.lib import readers

NAME = "device.between_programs_idle_share.olmo"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return readers.between_programs_pct(run)
