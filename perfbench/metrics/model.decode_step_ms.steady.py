"""Device time of decode-chunk programs per decode step, in the traced slice.
"""

from perfbench.lib import readers

NAME = "model.decode_step_ms.steady"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.decode_step_ms(run)
