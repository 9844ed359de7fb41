"""Share of device self time under ``head.unembed`` and ``sample``: the final norm, the 151,936-row
head and sampling over its logits.
"""

from perfbench.lib import scopes_dsa

NAME = "head.time_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.share_pct(run, ('head.unembed', 'sample'))
