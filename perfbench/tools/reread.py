"""Read a finished traced run again, with the readers of this tree or of
another: what a change to the readers did to every reading of one run.

    python3 perfbench/tools/reread.py <cell> [--root DIR] [--work DIR]

``run.py --trace 1`` keeps the run it measured as ``.work/<cell>/run.pkl``
beside the traces and their reductions. This loads it with the ``perfbench``
of ``--root`` (default: this checkout; a copy of the parent commit to compare
with), asks every ``per_layer`` reader that root's ``BENCHMARK.json`` gives
the cell, and prints ``{name: value}`` as one JSON line. Nothing runs on a
chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pickle
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--work", default=os.path.join(HERE, ".work"))
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    with open(os.path.join(args.work, args.cell, "run.pkl"), "rb") as f:
        run = pickle.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    out = {}
    for m in man["per_layer"]:
        if "workloads" in m and args.cell not in m["workloads"]:
            continue
        path = os.path.join(root, "perfbench", "metrics", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location(
            "reread_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[m["name"]] = mod.read(run)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
