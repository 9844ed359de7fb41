"""engine.submit() to slot held and prefill dispatched (worker.submitted ->
worker.admitted), median.
"""

from perfbench.lib import spanreaders

NAME = "engine.queue_wait_p50_ms"
LAYER = "engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return spanreaders.span_p50_ms(run, "worker.submitted", "worker.admitted")
