"""Least time at the chip's peaks for the latent attention's decode work in the traced
slice (the LIVE latent rows x 1,152 B a layer; the run's own family's ``mla_decode_cost``)
over its seconds there: the Kimi family over the latent decode kernel's own seconds, the
Xing family over the decode programs' self time under ``attn.mla`` (the layers' MLA
matrices left out of the bytes).
"""

from perfbench.lib import families

NAME = "mla.decode_roofline.overload"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "mla_decode_roofline_pct")
