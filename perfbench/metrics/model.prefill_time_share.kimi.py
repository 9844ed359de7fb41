"""The prefill programs' device time over the device's busy time in the traced slice: one
row a prefill of 256-6,144 tokens stalls the 32 decoding rows each time.
"""

from perfbench.lib import scopes_mla_share

NAME = "model.prefill_time_share.kimi"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.prefill_time_share_pct(run)
