"""Device time of decode-chunk programs per decode step, in the traced slice.
"""

from perfbench.lib import readers

NAME = "model.decode_step_ms.overload"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return readers.decode_step_ms(run)
