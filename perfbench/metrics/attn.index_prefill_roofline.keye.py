"""The index-score kernel (``index_scores_flash``) alone, from what ran in the slice: the (query, key)
pairs its runs' names give x 16 x 64 x 2 at the bf16 peak (``counts/dsa_moe.py``
``index_kernel_cost``) over the kernel's own self time.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.index_prefill_roofline.keye"
LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.index_prefill_roofline_pct(run)
