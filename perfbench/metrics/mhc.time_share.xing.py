"""Share of device self time under the ``resid.mhc`` scope: the three maps of every
sublayer (the phi product, 20 Sinkhorn rounds), the weighted read-out and the
write-back of the four residual streams, prefill and decode programs.
"""

from perfbench.lib import scopes_mhc

NAME = "mhc.time_share.xing"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mhc.mhc_share_pct(run)
