"""Stream frames the coordinator relayed per second of the window (stats RPC).
The streamed path bypasses the batcher, so there is no batch size to read.
"""


NAME = "coord.stream_frames_per_s.olmo"
LAYER = "coordinator"
UNIT = "frames/s"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    d = (run.coord_after.get("stream_frames", 0)
         - run.coord_before.get("stream_frames", 0))
    return d / run.window_s if d > 0 else None
