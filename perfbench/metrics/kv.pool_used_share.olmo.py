"""K|V pages of the pool in use, sampled through the window.
"""

from perfbench.lib import readers
from perfbench.lib.procs import MODEL

NAME = "kv.pool_used_share.olmo"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return readers.sampled(
        run, lambda m: 100.0 * m["models"][MODEL]["kv"]["utilization"])
