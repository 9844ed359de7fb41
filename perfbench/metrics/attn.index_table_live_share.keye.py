"""Index keys the decode steps had to score (``attn.index_rows_scored``: the live context) over the
index keys the program read for them (``attn.index_table_rows``: every slot's whole page table
and the side window), across the window.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.index_table_live_share.keye"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.ratio_pct(run, 'index_rows_scored', 'index_table_rows')
