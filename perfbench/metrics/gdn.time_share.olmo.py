"""Share of device self time under ``attn.gdn.step`` and ``attn.gdn.prefill``: the
Gated-DeltaNet layers' projections, convolution, gates, recurrence, gated norm and
out projection, in decode and in prefill.
"""

from perfbench.lib import scopes_gdn

NAME = "gdn.time_share.olmo"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_gdn.share_pct(run, ('attn.gdn.step', 'attn.gdn.prefill'))
