"""Share of device self time under the ``attn.mla`` scope: latent attention's five
projections at 64 heads, the absorbed decode over cached rows, the expanded prefill.
"""

from perfbench.lib import scopes_mla_share

NAME = "mla.time_share.kimi"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.share_pct(run, ('attn.mla',))
