"""Share of the traced slice the engine thread spends in program spans other than
its two waits (engine.harvest.wait, pump.idle_wait).
"""

from perfbench.lib import spanreaders

NAME = "engine.host_busy_share.olmo"
LAYER = "engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.host_busy_share_pct(run)
