"""Least time the chip could take for the traced slice's WHOLE decode steps (every kept
weight outside the routed experts and the head once a step, the experts TOUCHED x 88.1 MB,
the live latent rows x 1,152 B a layer: ``counts/mla_moe_share.py`` ``decode_stream_cost``)
at the v5e's peaks (the HBM peak binds) over the decode programs' device time.
"""

from perfbench.lib import scopes_mla_share

NAME = "model.decode_stream_roofline.kimi"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.decode_stream_roofline_pct(run)
