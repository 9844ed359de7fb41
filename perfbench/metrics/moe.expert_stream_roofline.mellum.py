"""Least time to read the experts that got a row in decode (``moe.experts_touched`` x one
expert's 12.39 MB, ``counts/swa_moe.py``; touched, never all held) at the HBM peak, over
the decode programs' self time under the ``moe.experts`` scope.
"""

from perfbench.lib import scopes_swa

NAME = "moe.expert_stream_roofline.mellum"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.expert_stream_roofline_pct(run)
