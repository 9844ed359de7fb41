"""Least time at the HBM peak to read and write the recurrent states a decode step
moves (``state.rows_updated`` x 2.28 MB a linear layer, read + written:
``counts/gdn_hybrid.py`` ``state_cost``) over the decode programs' self time under
the ``recurrence`` and ``state.update`` scopes.
"""

from perfbench.lib import scopes_gdn

NAME = "gdn.state_roofline.olmo"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.state_roofline_pct(run)
