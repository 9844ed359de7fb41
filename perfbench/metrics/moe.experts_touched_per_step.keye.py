"""Distinct experts that got a row, summed over the 6 layers, per decode step, across the window
(of 6 x 128 held).
"""

from perfbench.lib import scopes

NAME = "moe.experts_touched_per_step.keye"
LAYER = "model programs"
UNIT = "experts"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes.per_decode_step(run, 'moe', 'experts_touched')
