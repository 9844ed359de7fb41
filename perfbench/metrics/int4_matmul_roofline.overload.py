"""Least time the chip could take for the decode steps' int4 matmuls over their
measured kernel time.
"""

from perfbench.lib import readers

NAME = "int4_matmul_roofline.overload"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return readers.int4_roofline_pct(run)
