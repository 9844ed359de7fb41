"""Least time for the latent decode kernel's work in the traced slice (live rows x 1,152 B a
layer at 819 GB/s, or the absorbed products' 139 kFLOP a row a layer at 197 TFLOP/s,
whichever is larger: at 121 FLOP/B the HBM peak binds) over the kernel's own seconds.
"""

from perfbench.lib import scopes_mla_share

NAME = "mla.decode_roofline.kimi"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.mla_decode_roofline_pct(run)
