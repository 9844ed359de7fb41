"""The worker's engine build (weights from the seed, prepare) without its warm-
up.
"""


NAME = "setup.load_s"
LAYER = "set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return run.setup.get('load_s')
