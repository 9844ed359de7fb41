"""The prefill programs' device time over the device's busy time in the traced slice:
what the admissions (one row a prefill, buckets 1,024-16,384) take from the decode steps.
"""

from perfbench.lib import scopes_swa

NAME = "model.prefill_time_share.mellum"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_swa.prefill_time_share_pct(run)
