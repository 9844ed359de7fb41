"""Device time of the decode programs a decode step of the traced slice; the steps are the runs of
the head's product inside the slice (once a step; a chunk cut by the slice's edge counts for the
steps of it that ran).
"""

from perfbench.lib import scopes_dsa

NAME = "model.decode_step_ms.keye"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.decode_step_ms(run)
