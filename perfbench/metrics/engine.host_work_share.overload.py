"""Share of the slice's window the engine thread spends under an engine.* / pump.* span
other than its waits (slicereaders.WAIT_SPANS: engine.harvest.wait, pump.idle_wait and the two
blocking reads of a prefill's outputs, engine.first_tokens.wait and engine.prefill_counters.wait).
"""

from perfbench.lib import slicereaders

NAME = "engine.host_work_share.overload"
LAYER = "engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "out_tok_s"


def read(run):
    return slicereaders.under_span_share_pct(run, "engine_work_s")
