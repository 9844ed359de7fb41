"""Least time to read the experts that got a row (touched, never all held) and their rows at the
HBM peak (``counts/dsa_moe.py`` ``expert_stream_cost``) over the decode programs' self time under
``moe.experts``.
"""

from perfbench.lib import scopes_dsa

NAME = "moe.expert_stream_roofline.keye"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.expert_stream_roofline_pct(run)
