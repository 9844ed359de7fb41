"""Prefill dispatched to first token on the host (worker.admitted ->
worker.first_token), median. Also writes the run's TTFT split by span
(ttft-split.json in the work directory).
"""

from perfbench.lib import spanreaders

NAME = "engine.prefill_to_first_token_p50_ms"
LAYER = "engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    spanreaders.tile_coverage(run)
    return spanreaders.span_p50_ms(run, "worker.admitted",
                                   "worker.first_token")
