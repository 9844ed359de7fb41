"""K|V rows the decode steps attended to in the full-attention layers
(``attn.full_context_rows``) over rows the program says its attention read for them
(``attn.full_table_rows``), across the window.
"""

from perfbench.lib import families

NAME = "attn.full_table_live_share.overload"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "full_table_live_share_pct")
