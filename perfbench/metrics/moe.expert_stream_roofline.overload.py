"""Least time to read the experts that got a row in the slice's decode steps
(``moe.experts_touched`` x one expert's bytes, the run's own family's
``counts/<family>.py`` ``expert_stream_cost``; touched, never all held) and their rows at
the HBM peak, over the decode programs' self time under the ``moe.experts`` scope.
"""

from perfbench.lib import families

NAME = "moe.expert_stream_roofline.overload"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "expert_stream_roofline_pct")
