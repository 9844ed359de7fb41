"""Worker receive -> first token (worker_trace offsets), median."""

from perfbench.lib import readers

NAME = "engine.ttft_p50_ms"
LAYER = "engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return readers.pct(readers.engine_ttfts_ms(run), 50)
