"""Compile-cache entries that appeared while the window was open."""


NAME = "setup.compiles_in_window"
LAYER = "set-up"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run.setup.get('compiles_in_window')
