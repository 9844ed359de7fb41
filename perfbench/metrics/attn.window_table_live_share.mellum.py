"""K|V rows inside the window the decode steps attended to in a sliding layer
(``attn.window_context_rows``: min(context, 1,024) a live row a step) over the rows the
program says its attention read for them (``attn.window_table_rows``: the kernel's own
count of the window pages it copied, plus the side window).
"""

from perfbench.lib import scopes_swa

NAME = "attn.window_table_live_share.mellum"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.table_live_share_pct(run, 'window')
