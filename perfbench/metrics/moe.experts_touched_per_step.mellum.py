"""Distinct experts that got a row, summed over the 12 layers, per decode step, across
the window (of 12 x 64 held).
"""

from perfbench.lib import scopes

NAME = "moe.experts_touched_per_step.mellum"
LAYER = "model programs"
UNIT = "experts"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes.per_decode_step(run, 'moe', 'experts_touched')
