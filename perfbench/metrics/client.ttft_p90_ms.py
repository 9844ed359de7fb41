"""p90 of due time -> first frame at the client: recorded, not judged (a
queueing tail over a few dozen requests spreads past any bound the contract
allows).
"""

from perfbench.lib import readers

NAME = "client.ttft_p90_ms"
LAYER = "client + framing"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.pct(readers.ttfts_ms(run), 90)
