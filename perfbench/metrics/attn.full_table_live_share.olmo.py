"""K|V rows the decode steps attended to over rows the program says its attention
READ for them: counters ``attn.full_context_rows`` (a host sum over the harvested
steps) / ``attn.full_table_rows`` (counted in the program: the kernel's own count of
the pages it started a copy of, times the page, plus the side window). 100 % is what
reading in place should give, less the last page's and the side window's unused
rows; a kernel that copied dead pages or the whole table would read ~30 %.
"""

from perfbench.lib import scopes_gdn

NAME = "attn.full_table_live_share.olmo"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.table_live_share_pct(run)
