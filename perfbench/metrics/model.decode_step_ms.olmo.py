"""Device time of the decode-chunk programs in the traced slice per decode step:
the steps are the attention kernel's calls inside the slice over the full-attention
layers a step runs (``lib/scopes_gdn.py`` ``steps_in_slice``: a chunk cut by the
slice's edge counts for the steps of it that ran; no int4 call to count here).
"""

from perfbench.lib import scopes_gdn

NAME = "model.decode_step_ms.olmo"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.decode_step_ms(run)
