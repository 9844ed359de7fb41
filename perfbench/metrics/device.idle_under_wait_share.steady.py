"""Idle gaps of MIN_GAP_NS or more between device ops whose midpoint lies under one of the
engine thread's waits (slicereaders.WAIT_SPANS), over the slice's window: the device idle while the host waits
for it or for work.
"""

from perfbench.lib import slicereaders

NAME = "device.idle_under_wait_share.steady"
LAYER = "engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return slicereaders.under_span_share_pct(run, "gaps_under_wait_s")
