"""Distinct held experts that got a row, summed over the expert layers, per decode step,
across the window (counters ``moe.experts_touched`` over ``decode_steps``).
"""

from perfbench.lib import scopes

NAME = "moe.experts_touched_per_step.overload"
LAYER = "model programs"
UNIT = "experts"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes.per_decode_step(run, "moe", "experts_touched")
