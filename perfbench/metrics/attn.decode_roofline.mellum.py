"""Least time to read the LIVE K|V rows of both kinds of layer the decode steps attended to
(``counts/swa_moe.py`` ``attn_decode_cost``) at the HBM peak, over the decode programs'
self time under ``flash_decode`` (the kernel alone).
"""

from perfbench.lib import scopes_swa

NAME = "attn.decode_roofline.mellum"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.attn_decode_roofline_pct(run)
