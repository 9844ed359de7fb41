"""Device self time of the ops whose tf_op path holds a jax.named_scope of the program
(slicereaders.PROGRAM_SCOPES) over all device self time in the slice's window: how much of
what the device ran the program can name.
"""

from perfbench.lib import slicereaders

NAME = "device.scoped_time_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return slicereaders.share_pct(run, "scoped_self_s", "self_s")
