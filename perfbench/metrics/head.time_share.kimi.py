"""Share of device self time under ``head.unembed`` and ``sample``: the final norm, the
20,480-row slice of the head and sampling over its logits.
"""

from perfbench.lib import scopes_mla_share

NAME = "head.time_share.kimi"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.share_pct(run, ('head.unembed', 'sample'))
