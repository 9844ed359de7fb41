"""Window pages the live slots held over the pages the same contexts hold where nothing is
cut (the full layers' pool), both summed by the allocator over the window's decode
dispatches (``kv.window_pages_held_sum`` / ``kv.window_pages_uncut_sum``): what the
sliding layers' cache costs against keeping every row.
"""

from perfbench.lib import scopes_swa

NAME = "attn.window_pages_held_share.mellum"
LAYER = "paged KV"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.window_pages_held_share_pct(run)
