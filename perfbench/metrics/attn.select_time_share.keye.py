"""Share of device self time under ``attn.select``, both kinds of program: the top-2,048 of a
decode step's scores (``lax.top_k``) and a prefill's counted threshold and mask.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.select_time_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.share_pct(run, ('attn.select',))
