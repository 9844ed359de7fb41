"""What the coordinator hop adds before the first token (RequestTrace received
-> first_frame less the worker's own receive -> first_token), median.
"""

from perfbench.lib import readers

NAME = "coord.overhead_p50_ms"
LAYER = "coordinator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.pct(readers.coord_overheads_ms(run), 50)
