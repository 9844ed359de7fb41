"""Least time the chip could take for the decode steps' int4 matmuls (HBM-
bound: every weight byte once per step) over their measured kernel time.
"""

from perfbench.lib import readers

NAME = "int4_matmul_roofline.steady"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.int4_roofline_pct(run)
