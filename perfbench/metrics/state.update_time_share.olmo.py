"""Share of device self time under ``state.update``: the masked write-back of the
per-slot recurrent state and conv tail (decode) and their scatter into the slots
(prefill).
"""

from perfbench.lib import scopes_gdn

NAME = "state.update_time_share.olmo"
LAYER = "paged KV"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.share_pct(run, ('state.update',))
