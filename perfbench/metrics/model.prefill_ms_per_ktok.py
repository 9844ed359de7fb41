"""Device time of prefill programs per thousand prompt tokens admitted, in the
traced slice.
"""

from perfbench.lib import readers

NAME = "model.prefill_ms_per_ktok"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.prefill_ms_per_ktok(run)
