"""Seconds of the workers' backend compile requests while the window was open
(compiles and compile-cache loads): the count beside it says how many, this
says whether they were cache loads of milliseconds or a compile of seconds.
"""

from perfbench.lib import spanreaders

NAME = "setup.compile_in_window_s"
LAYER = "set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return spanreaders.compile_delta(run, "backend_compile_s")
