"""A stream's wait for a pooled connection to its worker (RequestTrace
dispatched -> conn_acquired), median: above capacity this is where the queue is.
"""

from perfbench.lib import spanreaders

NAME = "coord.pool_wait_p50_ms.olmo"
LAYER = "coordinator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.span_p50_ms(run, "dispatched", "conn_acquired")
