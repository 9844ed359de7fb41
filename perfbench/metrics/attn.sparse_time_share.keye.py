"""Share of device self time under ``attn.gather`` and ``attn.sparse``, both kinds of program: the
gather of the selected K|V rows and the attention over them; in a prefill the masked attention.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.sparse_time_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.share_pct(run, ('attn.gather', 'attn.sparse'))
