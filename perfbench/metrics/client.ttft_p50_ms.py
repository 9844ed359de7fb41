"""Median time from a request's due time to its first stream frame at the
client.
"""

from perfbench.lib import readers

NAME = "client.ttft_p50_ms"
LAYER = "client + framing"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.pct(readers.ttfts_ms(run), 50)
