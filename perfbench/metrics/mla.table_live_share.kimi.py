"""Latent rows the decode steps attended to (``mla.decode_context_rows``) over rows the
program says its attention read for them (``mla.decode_table_rows``: the pool pages the
latent kernel started a copy of x 128, plus the side window), across the window.
"""

from perfbench.lib import scopes_mla_share

NAME = "mla.table_live_share.kimi"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.table_live_share_pct(run)
