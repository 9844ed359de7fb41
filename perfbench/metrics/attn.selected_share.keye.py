"""K|V rows the decode steps' attention read (``attn.rows_selected``: the program's own count, at
most 2,048 a sequence a layer) over the rows of context a dense layer would have read
(``attn.full_context_rows``), across the window.
"""

from perfbench.lib import scopes_dsa

NAME = "attn.selected_share.keye"
LAYER = "model programs"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "out_tok_s"


def read(run):
    return scopes_dsa.ratio_pct(run, 'rows_selected', 'full_context_rows')
