"""Share of device self time under ``head.unembed`` and ``sample``: the final norm, the
131,072-row head and sampling over its logits.
"""

from perfbench.lib import scopes

NAME = "head.time_share.xing"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes.share_pct(run, ("head.unembed", "sample"))
