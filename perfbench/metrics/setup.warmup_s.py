"""The engine's own warm-up grid (admission batch x prefill bucket): compiles
on a checkout's first run, reads the compile cache after.
"""


NAME = "setup.warmup_s"
LAYER = "set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return run.setup.get('warmup_s')
