"""Top-k choices that landed on the 12 experts this chip holds over all choices (prefill and
decode), across the window: 12 / 384 = 3.1 % under uniform routing.
"""

from perfbench.lib import scopes_mla_share

NAME = "moe.held_assignment_share.kimi"
LAYER = "model programs"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_mla_share.held_assignment_share_pct(run)
