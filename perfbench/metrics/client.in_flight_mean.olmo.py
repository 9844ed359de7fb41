"""Requests sent and not yet finished, averaged over the window's sampling
instants.
"""

from perfbench.lib.loadgen import in_flight_at
from perfbench.lib.stats import mean

NAME = "client.in_flight_mean.olmo"
LAYER = "client + framing"
UNIT = "requests"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "out_tok_s"


def read(run):
    return mean([in_flight_at(run.records, s["t"]) for s in run.samples])
