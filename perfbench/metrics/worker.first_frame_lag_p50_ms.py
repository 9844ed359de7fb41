"""First token on the host to the first stream frame sent (worker.first_token ->
worker.first_frame_sent), median: thread hop and framing.
"""

from perfbench.lib import spanreaders

NAME = "worker.first_frame_lag_p50_ms"
LAYER = "worker + pump"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return spanreaders.span_p50_ms(run, "worker.first_token",
                                   "worker.first_frame_sent")
