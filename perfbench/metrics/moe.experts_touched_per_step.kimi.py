"""Distinct held experts that got a row, summed over the 6 expert layers (of 6 x 12 held),
per decode step, across the window.
"""

from perfbench.lib import scopes

NAME = "moe.experts_touched_per_step.kimi"
LAYER = "model programs"
UNIT = "experts"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes.per_decode_step(run, "moe", "experts_touched")
