"""Share of device self time under ``attn.index``, both kinds of program: the indexer's three
projections, the index keys read through the page table and the index scores.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.index_time_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.share_pct(run, ('attn.index',))
