"""Latent rows the decode steps attended to (``mla.decode_context_rows``) over rows the
program says its attention read for them (``mla.decode_table_rows``), across the window.
"""

from perfbench.lib import families

NAME = "mla.table_live_share.overload"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "mla_table_live_share_pct")
