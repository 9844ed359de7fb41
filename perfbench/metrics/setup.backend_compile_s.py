"""Seconds the worker spent in backend compile requests from process start to
window open (compiles and compile-cache loads).
"""

from perfbench.lib import spanreaders

NAME = "setup.backend_compile_s"
LAYER = "set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return spanreaders.compile_at_open(run, "backend_compile_s")
