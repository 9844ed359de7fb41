"""Live decode slots over max_slots, sampled through the window.
"""

from perfbench.lib import readers
from perfbench.lib.procs import MODEL

NAME = "engine.occupancy.olmo"
LAYER = "engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    slots = float(run.config['serve']['max_batch_size'])
    return readers.sampled(
        run, lambda m: 100.0 * m["models"][MODEL]["live_slots"] / slots)
