"""Device time of the decode programs a decode step of the traced slice, as the run's own
family counts the slice's steps (``lib/families.py`` ``scope_reading``): the dense int4
family by its int4 kernel calls, the Ling and Xing families by the engine's
``decode_steps`` between the slice's two stamps, the others by their attention kernel's
(Keye: the head's) runs inside the slice.
"""

from perfbench.lib import families

NAME = "model.decode_step_ms.overload"
LAYER = "model programs"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "decode_step_ms")
