"""bytes_in_use on the fullest chip when the window closed."""

from perfbench.lib import readers

NAME = "device.hbm_in_use_gb"
LAYER = "device"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return readers.hbm_in_use_gb(run)
