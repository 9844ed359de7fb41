"""The prefill programs' device time over the device's busy time in the traced slice:
what a prompt of up to 4,096 tokens (64 chunks of the recurrence) takes from eight
decoding streams.
"""

from perfbench.lib import scopes_gdn

NAME = "model.prefill_time_share.olmo"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.prefill_time_share_pct(run)
