"""The prefill programs' device time over the device's busy time in the traced slice: what the
admissions (one row a prefill, buckets 4,096-32,768, index scores and a counted threshold a block
of queries) take from the decode steps.
"""

from perfbench.lib import scopes_dsa

NAME = "model.prefill_time_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.prefill_time_share_pct(run)
