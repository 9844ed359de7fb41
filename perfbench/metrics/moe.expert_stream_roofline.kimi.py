"""Least time to read the experts that got a row in decode (``moe.experts_touched`` x one
expert's 88.1 MB, ``counts/mla_moe_share.py``; touched, never all held) at the HBM peak,
over the decode programs' self time under the ``moe.experts`` scope.
"""

from perfbench.lib import scopes_mla_share

NAME = "moe.expert_stream_roofline.kimi"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.expert_stream_roofline_pct(run)
