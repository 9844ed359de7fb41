"""Share of device self time under ``attn.gdn.prefill``: the chunked (WY) form of the
delta rule and what surrounds it, over whole prompts.
"""

from perfbench.lib import scopes_gdn

NAME = "gdn.prefill_time_share.olmo"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.share_pct(run, ('attn.gdn.prefill',))
