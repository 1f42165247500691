#!/usr/bin/env python3
"""The control at a cell's own size, on the chip: one run of
``bench/run.py --control`` per seed, each a process of its own, with the
bfloat16 reference's picks judged in the program's place.

    python3 bench/tests/control_on_chip.py --workload qwen3-0.6b.chat \\
        --seconds 40 --seeds 11 12 13

Prints, per seed, the control's numbers and the program's (reported by the
same run) beside the cell's limits, and exits 0 only where every control
run comes out not correct and every program reading is within its limits.
This process does not touch JAX: each run holds the chip alone.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def one(workload: str, seed: int, seconds: float) -> dict:
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", "0", "--control"],
        cwd=ROOT, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode or not lines:
        raise SystemExit(f"seed {seed}: exit {proc.returncode}\n"
                         f"{proc.stderr[-2000:]}")
    return json.loads(lines[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    ok = True
    for seed in args.seeds:
        r = one(args.workload, seed, args.seconds)
        limits = {k: v["limit"] for k, v in r["compared"].items()}
        program_ok = (all(r["program"][k] <= limits[k] for k in r["program"])
                      and all(v["value"] <= v["limit"]
                              for k, v in r["compared"].items()
                              if k not in r["program"]))
        ok &= program_ok and not r["correct"]
        print(json.dumps({"seed": seed, "control_correct": r["correct"],
                          "program_correct": program_ok,
                          "control": {k: r["compared"][k]["value"]
                                      for k in r["program"]},
                          "program": r["program"], "limits": limits,
                          "checked_tokens": r["checked_tokens"]}),
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
