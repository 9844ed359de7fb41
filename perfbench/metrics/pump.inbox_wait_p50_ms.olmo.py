"""Handler entry to engine.submit() on the pump thread (worker.received ->
worker.submitted), median: the wait for the engine thread to reach the inbox.
"""

from perfbench.lib import spanreaders

NAME = "pump.inbox_wait_p50_ms.olmo"
LAYER = "worker + pump"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.span_p50_ms(run, "worker.received", "worker.submitted")
