"""The prefill programs' device time over the device's busy time in the traced slice: a
prefill stalls every decoding row each time it runs.
"""

from perfbench.lib import families

NAME = "model.prefill_time_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "prefill_time_share_pct")
