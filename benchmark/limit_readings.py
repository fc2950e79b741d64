"""Readings that the limit of ``correct`` is set from, for one cell.

    python3 -m benchmark.limit_readings --workload <cell> --seconds <s> --seeds 1,2,3,...

In one process (the set-up is long), for each seed: a whole run of the
cell as the benchmark makes it, then, on the same sample of served
requests, the gaps of the program (the lower reading) and of the
control: the reference put in the program's place and computed in float8
e4m3, one precision below the configuration's bfloat16, whose tokens are
the ones it puts first at each position (the upper reading).  Each row
gives the program's verdict (``correct``) and the control's
(``control_correct``), both against the cell's limits file.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from benchmark import run as runmod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        keep = {}
        res = runmod.run(["--workload", args.workload, "--seed", str(seed),
                          "--seconds", str(args.seconds), "--trace", "0"],
                         keep=keep, control=args.control)
        row = {"seed": seed, "correct": res["correct"],
               "control_correct": res["control_correct"],
               "program": runmod.gap_stats(keep["gaps"]),
               "control": runmod.gap_stats(keep["control_gaps"]),
               "tokens": int(keep["gaps"].size), "agreement": keep["agreement"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        rows.append(row)
        print("LIMITS " + json.dumps(row), file=sys.stderr)
        gc.collect()
    summary = {}
    for k in rows[0]["program"]:
        lo = max(r["program"][k] for r in rows)
        hi = min(r["control"][k] for r in rows)
        summary[k] = {"lower_reading": lo, "upper_reading": hi,
                      "ratio": hi / lo if lo else None}
    print(json.dumps({"workload": args.workload, "readings": summary, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
