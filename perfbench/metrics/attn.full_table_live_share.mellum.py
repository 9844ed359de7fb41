"""K|V rows the decode steps attended to in a full layer (``attn.full_context_rows``) over
the rows the program says its attention read for them (``attn.full_table_rows``: the
kernel's own count of the pages it copied, plus the side window).
"""

from perfbench.lib import scopes_swa

NAME = "attn.full_table_live_share.mellum"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.table_live_share_pct(run, 'full')
