"""The same least time over the self time of the Mosaic grouped-matmul kernel's own ops
(scope ``gmm`` inside ``moe.experts``) in decode programs.
"""

from perfbench.lib import scopes_dsa

NAME = "moe_gmm_roofline.keye"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.expert_stream_roofline_pct(run, 'gmm')
