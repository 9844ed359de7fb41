"""1 - union of device-op intervals over the traced slice.
"""

from perfbench.lib import readers

NAME = "device.idle_share.olmo"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return readers.idle_share_pct(run)
