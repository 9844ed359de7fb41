"""A stream's wait for a pooled connection to its worker (RequestTrace
dispatched -> conn_acquired), median.
"""

from perfbench.lib import spanreaders

NAME = "coord.pool_wait_p50_ms"
LAYER = "coordinator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def read(run):
    return spanreaders.span_p50_ms(run, "dispatched", "conn_acquired")
