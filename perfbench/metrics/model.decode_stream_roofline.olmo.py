"""Least time at the HBM peak for a whole decode step (every kept weight and the head
once, the LIVE K|V rows from ``attn.full_context_rows`` x 15,360 B a full layer, the
live states read and written: ``counts/gdn_hybrid.py`` ``decode_stream_cost``) over the
decode programs' device time a step. The whole step's share: what bounds any later
claim in this cell.
"""

from perfbench.lib import scopes_gdn

NAME = "model.decode_stream_roofline.olmo"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.decode_stream_roofline_pct(run)
