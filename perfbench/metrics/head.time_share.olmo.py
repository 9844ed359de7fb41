"""Share of device self time under ``head.unembed`` and ``sample``: the final norm, the
100,352-row head and sampling over its logits.
"""

from perfbench.lib import scopes_gdn

NAME = "head.time_share.olmo"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.share_pct(run, ('head.unembed', 'sample'))
