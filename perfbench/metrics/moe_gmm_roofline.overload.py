"""The same least time as ``moe.expert_stream_roofline`` over the self time of the Mosaic
grouped-matmul kernel's own ops (scope ``gmm`` inside ``moe.experts``) in decode programs.
"""

from perfbench.lib import families

NAME = "moe_gmm_roofline.overload"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "expert_stream_roofline_pct", "gmm")
