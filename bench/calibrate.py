"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 101,102,... --control-seeds 101,102,103

Runs the cell once per seed in one process, as ``bench/run.py`` does, and
prints one JSON line per seed: the widest gap of the served tokens (the
program's reading) and, for the control seeds, the widest gap of the tokens
that the fp8 reference puts first at the same positions (the control's
reading) and whether the cell's limit passes it (``control_correct``,
which has to be false: the exit code is 1 where it is true).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run  # noqa: E402
from bench.core import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        run.require_chips(int(cell.entry["chips"]))
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    passed = []             # seeds whose control came out correct
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.run_cell(args.workload, seed, args.seconds, False,
                         control=seed in controls)
        v = r["_verdict"]
        print(json.dumps({"seed": seed, "gap": v["logit_gap"],
                          "control_gap": v.get("control_gap"),
                          "control_correct": v.get("control_correct"),
                          "served_tokens": v["served_tokens"],
                          "requests": v["requests"], "correct": r["correct"],
                          "metrics": {k: m["value"] for k, m in r["metrics"].items()},
                          "window": r["_window"]}), flush=True)
        if v.get("control_correct"):
            passed.append(seed)
    if passed:
        print(f"calibrate: the control passes the limit on seeds {passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
