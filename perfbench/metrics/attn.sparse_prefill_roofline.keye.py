"""The masked prefill kernel (``sparse_prefill_flash``) alone, a FLOOR: the fewest (query, key) pairs
its runs in the slice can have computed (``counts/dsa_moe.py`` ``sparse_prefill_least_pairs``: a run's
name gives its chunk and bucket, not the prompt's length) x 32 x 128 x 4 at the bf16 peak over the
kernel's own self time.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.sparse_prefill_roofline.keye"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.sparse_prefill_roofline_pct(run)
