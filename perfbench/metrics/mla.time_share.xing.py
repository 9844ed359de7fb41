"""Share of device self time under the ``attn.mla`` scope: latent attention's
projections, the absorbed decode over cached rows, the expanded prefill.
"""

from perfbench.lib import scopes

NAME = "mla.time_share.xing"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes.share_pct(run, ('attn.mla',))
