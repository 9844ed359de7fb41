"""Part of tracered's window (first to last event of every plane, the host's too) that lies
outside the slice: 1 - window / window_s, where the window is [start anchor, stop anchor]
(the two clock.anchor events of the worker's profile RPC) cut to the device's first op start
and last op end. The part of device.idle_share.* that is not the device's.
"""

from perfbench.lib import slicereaders

NAME = "device.trace_overhang_share.steady"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return slicereaders.trace_overhang_share_pct(run)
