"""Share of device self time under the model programs' attn.kv_gather and
attn.kv_update scopes: what a step copies out of and into the pages, whatever
the family keeps there (dense K/V rows, latent rows, a full layer's K|V beside
recurrent state that is not paged). None for a program without the scopes.
"""

from perfbench.lib import spanreaders

NAME = "kv.copy_time_share.overload"
LAYER = "paged KV"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return spanreaders.kv_copy_share_pct(run)
