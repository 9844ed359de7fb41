"""Least time at the HBM peak to read the LIVE K|V rows a decode step attends to
(``attn.full_context_rows`` x 15,360 B a full layer; live rows, never the table:
``counts/gdn_hybrid.py`` ``full_decode_cost``) over the decode programs' self time
under the ``flash_decode`` scope: the kernel at 30 K/V heads and contexts up to 6k.
Rows a step between the worker's two stamps of the traced slice (``counters.json``),
steps = the kernel's own calls in the slice (``lib/scopes_gdn.py``).
"""

from perfbench.lib import scopes_gdn

NAME = "attn.full_decode_roofline.olmo"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.full_decode_roofline_pct(run)
