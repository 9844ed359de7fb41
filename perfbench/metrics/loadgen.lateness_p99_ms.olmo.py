"""How late the load generator sent the requests due in the window, p99."""

from perfbench.lib import readers
from perfbench.lib.loadgen import lateness_s

NAME = "loadgen.lateness_p99_ms.olmo"
LAYER = "client + framing"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "out_tok_s"


def read(run):
    return readers.pct([x * 1e3 for x in lateness_s(run.records)], 99)
