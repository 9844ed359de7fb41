"""``tools/rehearse.py`` for the rehearsal this PR's family brings, as a
file of its own (``rehearse.py`` belongs to the accepted benchmark and stays
as it is): ``run.py`` on the CPU with the cell, ``keye-tiny.json`` and its
mix taken from ``perfbench/rehearse/``.

    JAX_PLATFORMS=cpu python3 perfbench/tools/rehearse_keye.py tiny-keye-chat \\
        --seed 7 --seconds 10 --trace 1

A benchmark PR folds it in by moving the two files, appending the entry to
``rehearsal.json`` and deleting this file.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.tools import rehearse  # noqa: E402

# name -> (configuration, mix), both files of perfbench/rehearse/
REHEARSALS = {
    "tiny-keye-chat": ("keye-tiny", "chat-tiny-keye"),
}


def main(argv=None) -> int:
    rehearse.REHEARSALS.update(REHEARSALS)
    try:
        return rehearse.main(argv)
    finally:
        for name in REHEARSALS:
            rehearse.REHEARSALS.pop(name, None)


if __name__ == "__main__":
    sys.exit(main())
