"""1 - union of device-op intervals over the slice's window ([start anchor, stop anchor] cut
to the device's first op start and last op end): the device's own idle time.
"""

from perfbench.lib import slicereaders

NAME = "device.idle_in_window_share.overload"
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return slicereaders.share_pct(run, "idle_s")
