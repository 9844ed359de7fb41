"""Share of device self time under ``head.unembed`` and ``sample``: the final norm, the
head (a slice of it where the configuration cuts the vocabulary) and sampling over its
logits.
"""

from perfbench.lib import families

NAME = "head.time_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "share_pct",
                                  ("head.unembed", "sample"))
