"""Share of device self time under the ``attn.mla`` scope: the latent projections, the
absorbed attention over the latent rows (decode) or the latent prefill kernel, and the
out projection.
"""

from perfbench.lib import families

NAME = "mla.time_share.overload"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return families.scope_reading(run, "share_pct", ("attn.mla",))
