"""The int4 kernel's share of device busy time in the traced slice."""

from perfbench.lib import readers

NAME = "int4_matmul_time_share.steady"
LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.int4_time_share_pct(run)
