"""Median per-request time per output token above the knee: recorded, not
judged.
"""

from perfbench.lib import readers

NAME = "client.tpot_p50_ms.olmo"
LAYER = "client + framing"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "out_tok_s"


def read(run):
    return readers.pct(readers.tpots_ms(run), 50)
