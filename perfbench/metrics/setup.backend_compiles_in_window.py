"""Programs the workers' backends were asked for while the window was open (the
compiler's own counter: XLA compiles and compile-cache loads, however short).
"""

from perfbench.lib import spanreaders

NAME = "setup.backend_compiles_in_window"
LAYER = "set-up"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return spanreaders.compile_delta(run, "backend_compiles")
