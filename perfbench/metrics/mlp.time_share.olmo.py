"""Share of device self time under ``mlp.dense``: the dense SwiGLU of every layer,
the plain bf16 weight stream in decode.
"""

from perfbench.lib import scopes_gdn

NAME = "mlp.time_share.olmo"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.share_pct(run, ('mlp.dense',))
