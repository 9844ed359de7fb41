"""Least time the chip could take for the traced slice's WHOLE decode steps (every kept
weight outside the routed experts and the head once a step, the experts TOUCHED, the LIVE
cache rows and states: the run's own family's ``counts/<family>.py``
``decode_stream_cost``) at the chip's peaks over the decode programs' device time. The
whole step's share of the peak: what bounds any later claim in a cell.
"""

from perfbench.lib import families

NAME = "model.decode_stream_roofline.overload"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "decode_stream_roofline_pct")
