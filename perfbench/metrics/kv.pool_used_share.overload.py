"""Pages of the page pool in use (the pool's own ``utilization``), sampled
through the window: dense K/V, latent rows or the full layers' K|V, as the
family's cache holds them (``counts/<family>.py`` ``CACHE``)."""

from perfbench.lib import readers
from perfbench.lib.procs import MODEL

NAME = "kv.pool_used_share.overload"
LAYER = "paged KV"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return readers.sampled(
        run, lambda m: 100.0 * m["models"][MODEL]["kv"]["utilization"])
