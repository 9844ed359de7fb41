"""Stream dispatches holding or waiting for a worker connection (coordinator
stats gauge streams_in_flight), mean of the window's samples.
"""

from perfbench.lib import spanreaders

NAME = "coord.streams_in_flight_mean.xing"
LAYER = "coordinator"
UNIT = "requests"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.coord_gauge_mean(run, "streams_in_flight")
