"""The pump's in_flight, sampled through the window, per worker."""

from perfbench.lib import readers
from perfbench.lib.procs import MODEL

NAME = "pump.in_flight_mean.xing"
LAYER = "worker + pump"
UNIT = "requests"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return readers.sampled(
        run, lambda m: float(m["pumps"][MODEL]["in_flight"]))
