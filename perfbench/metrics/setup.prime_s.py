"""The benchmark's priming requests through the served path (decode context
buckets, deferred admission, the repeatability pair).
"""


NAME = "setup.prime_s"
LAYER = "set-up"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.setup.get('prime_s')
