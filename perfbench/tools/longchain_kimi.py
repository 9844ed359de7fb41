"""``tools/longchain_xing.py`` for the ``kimik25-agent-overload`` cell: one
chain at the cell's own lengths (4,096 + 64 tokens) served through
``ContinuousEngine`` alone and again among 31 other live rows (the same
tokens both ways), judged by ``reference/check.py``'s ``judge`` against the
``mla_moe_share`` family's float32 reference, with the controls that must
fall OUTSIDE the limits (``routed_scaling_factor`` left at 1, YaRN's factor
missing, ``experts_held`` shifted to (1, 13), the shared expert dropped) and
the whole reference in bfloat16. A builder's tool, run on the chip in ONE
process:

    python3 perfbench/tools/longchain_kimi.py [--chains 16] [--seed 7]

The tool itself is ``longchain_xing.py`` (it asks the configuration's family
for its reference, its limits and its controls): this file gives it this
cell's defaults, and any argument given here wins. ``--config kimi-tiny
--prompt 60 --out 16 --others 11`` rehearses the control flow on the CPU from
``perfbench/rehearse/`` (it says FAIL there: at width 64 no control moves a
token). The readings land in ``chiprun_out/longchain_kimi.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.tools import longchain_xing  # noqa: E402

DEFAULTS = ["--config", "kimi-k2.5-ep32-pp1", "--others", "31",
            "--controls", "routed_scale_one,no_yarn_softmax_factor,"
            "experts_shifted,no_shared_expert,bfloat16"]


def verdict(rows, ref) -> bool:
    """``longchain_xing``'s rule with this family's two lists: every served
    chain inside its limits, every control outside them, but for the
    reading the family says no chain of its separates, short
    (``NOT_SEPARATED``) or long (``LONG_NOT_SEPARATED``): the whole
    reference in bfloat16. All four wrong models are held to it."""
    ok = True
    for row in rows:
        long_chain = row["chain"] in ("alone", "among")
        spared = ref.LONG_NOT_SEPARATED if long_chain else ref.NOT_SEPARATED
        if row["against"] == "reference":
            ok = ok and row["judge_ok"]
        elif row["against"] not in spared:
            ok = ok and not row["judge_ok"]
    return ok


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    longchain_xing.main(DEFAULTS + argv)
    src = os.path.join(ROOT, "chiprun_out", "longchain_xing.json")
    out = os.path.join(ROOT, "chiprun_out", "longchain_kimi.json")
    os.replace(src, out)
    with open(out) as f:
        rows = json.load(f)["rows"]
    from perfbench.lib import families, session
    from perfbench.tools import rehearse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--config")
    config = ap.parse_known_args(DEFAULTS + argv)[0].config
    path = os.path.join(ROOT, "perfbench", "configs", f"{config}.json")
    ref = families.reference(session.load_config(config)
                             if os.path.exists(path)
                             else rehearse.load(config))
    ok = verdict(rows, ref)
    print(f"longchain_kimi: {'PASS' if ok else 'FAIL'} by this family's "
          f"lists (short chains spare {ref.NOT_SEPARATED}, the long chain "
          f"{ref.LONG_NOT_SEPARATED})", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
