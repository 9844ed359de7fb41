"""Requests the workers shed or failed during the window (overloaded + deadline
+ error counts).
"""

from perfbench.lib import readers

NAME = "worker.shed.olmo"
LAYER = "worker + pump"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    deltas = [readers.counter_delta(run, [k]) for k in (
        "overloaded_count", "deadline_expired_count", "error_count")]
    return None if None in deltas else sum(deltas)
