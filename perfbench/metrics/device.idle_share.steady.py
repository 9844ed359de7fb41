"""1 - union of device-op intervals over the traced slice."""

from perfbench.lib import readers

NAME = "device.idle_share.steady"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.idle_share_pct(run)
