"""Least time at the HBM peak for a whole decode step (the experts that got a row x 9.44 MB, every
layer's attention and indexer matrices, norms and router, the head once, the LIVE index keys x
128 B and the K|V rows selected x 2,048 B: ``counts/dsa_moe.py`` ``decode_stream_cost``) over the
decode programs' device time a step. The whole step's share: what bounds any later claim here.
"""

from perfbench.lib import scopes_dsa

NAME = "model.decode_stream_roofline.keye"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.decode_stream_roofline_pct(run)
