"""Top-k choices that landed on the experts this chip holds over all choices (prefill and
decode), across the window: held / all experts under uniform routing.
"""

from perfbench.lib import families

NAME = "moe.held_assignment_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "held_assignment_share_pct")
