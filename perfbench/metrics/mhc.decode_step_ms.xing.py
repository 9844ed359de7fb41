"""Device self time under ``resid.mhc`` in the decode programs per decode step (14
sublayers a step): 0.1 ms if the rounds fuse, ~1 ms as a chain of tiny ops.
"""

from perfbench.lib import scopes_mhc

NAME = "mhc.decode_step_ms.xing"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mhc.mhc_ms_per_decode_step(run)
