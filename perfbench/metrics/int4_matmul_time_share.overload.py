"""The int4 kernel's share of device busy time in the traced slice."""

from perfbench.lib import readers

NAME = "int4_matmul_time_share.overload"
LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return readers.int4_time_share_pct(run)
