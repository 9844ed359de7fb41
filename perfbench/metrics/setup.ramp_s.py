"""Load before the window opens, so that it opens on a loaded system."""


NAME = "setup.ramp_s"
LAYER = "set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.setup.get('ramp_s')
