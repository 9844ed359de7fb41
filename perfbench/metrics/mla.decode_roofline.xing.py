"""Least time at the HBM peak to read the LIVE latent rows a decode step attends to
(counter ``mla.decode_context_rows`` x 1152 B a layer; live rows, never the table:
``counts/xing4_mhc.py`` ``mla_decode_cost``), over the decode programs' self time under
the ``attn.mla`` scope. The layers' MLA matrices are not in the bytes (the trace
charges part of their read outside the scope): bytes and seconds are of the same work.
"""

from perfbench.lib import scopes_mhc

NAME = "mla.decode_roofline.xing"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mhc.mla_decode_roofline_pct(run)
