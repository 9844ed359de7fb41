"""peak_bytes_in_use on the fullest chip after the window."""


NAME = "device.peak_hbm_gb"
LAYER = "device"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
