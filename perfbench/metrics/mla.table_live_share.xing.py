"""Latent rows the decode steps attended to over rows the decode body read for them
(the whole page table, every step): counters ``mla.decode_context_rows`` /
``mla.decode_table_rows``.
"""

from perfbench.lib import scopes_mhc

NAME = "mla.table_live_share.xing"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_mhc.table_live_share_pct(run)
