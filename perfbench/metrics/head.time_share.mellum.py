"""Share of device self time under ``head.unembed`` and ``sample``: the final norm, the
98,304-row head and sampling over its logits.
"""

from perfbench.lib import scopes_swa

NAME = "head.time_share.mellum"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.share_pct(run, ('head.unembed', 'sample'))
