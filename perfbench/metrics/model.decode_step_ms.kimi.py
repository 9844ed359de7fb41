"""Device time of the decode programs a decode step of the traced slice; the steps are the
latent decode kernel's calls inside the slice over the 7 MLA layers a step runs (a chunk
cut by the slice's edge counts for the steps of it that ran).
"""

from perfbench.lib import scopes_mla_share

NAME = "model.decode_step_ms.kimi"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.decode_step_ms(run)
