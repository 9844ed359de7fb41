"""Least time at the HBM peak for a whole decode step (the experts that got a row x 12.39 MB,
every layer's attention matrices, norms and router, the head once, the LIVE K|V rows x
2,048 B: full layers at the context, sliding layers at min(context, 1,024):
``counts/swa_moe.py`` ``decode_stream_cost``) over the decode programs' device time a step.
The whole step's share: what bounds any later claim in this cell.
"""

from perfbench.lib import scopes_swa

NAME = "model.decode_stream_roofline.mellum"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.decode_stream_roofline_pct(run)
