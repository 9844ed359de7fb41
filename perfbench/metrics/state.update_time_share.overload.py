"""Share of device self time under the ``state.update`` scope: the recurrent state and
conv tail written back per step (masked by ``active``) or per prefill.
"""

from perfbench.lib import families

NAME = "state.update_time_share.overload"
LAYER = "paged KV"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "share_pct", ("state.update",))
