"""Idle gaps of MIN_GAP_NS or more between device ops whose midpoint lies under a span of the
engine thread that is not a wait, over the slice's window: the idle the host's work costs.
"""

from perfbench.lib import slicereaders

NAME = "device.idle_under_host_work_share.steady"
LAYER = "engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return slicereaders.under_span_share_pct(run, "gaps_under_work_s")
