"""Stream dispatches waiting for a pooled worker connection (coordinator stats
gauge pool_waiting), mean of the window's samples.
"""

from perfbench.lib import spanreaders

NAME = "coord.pool_waiting_mean.olmo"
LAYER = "coordinator"
UNIT = "requests"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.coord_gauge_mean(run, "pool_waiting")
