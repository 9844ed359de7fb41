"""The prefill programs' device time over the device's busy time in the traced slice:
what long prompts take from eight decoding streams.
"""

from perfbench.lib import scopes_mhc

NAME = "model.prefill_time_share.xing"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mhc.prefill_time_share_pct(run)
