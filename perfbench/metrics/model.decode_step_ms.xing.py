"""Device time of the decode-chunk programs in the traced slice per decode step:
the slice's decode programs times the window's steps a chunk (the engine's
``decode_steps`` / ``decode_chunks`` counters; no int4 call to count here).
"""

from perfbench.lib import scopes

NAME = "model.decode_step_ms.xing"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes.decode_step_ms(run)
