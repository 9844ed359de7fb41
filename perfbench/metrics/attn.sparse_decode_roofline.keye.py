"""Least time to read the index keys the decode steps READ (the table's rows, padding included) and
the K|V rows they gathered at the HBM peak (``counts/dsa_moe.py`` ``sparse_decode_cost``) over the
decode programs' self time under ``attn.index``, ``attn.select``, ``attn.gather`` and ``attn.sparse``.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.sparse_decode_roofline.keye"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.sparse_decode_roofline_pct(run)
