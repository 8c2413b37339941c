#!/usr/bin/env python3
"""Repeat a cell as the driver's check does and print each metric's spread.

    python3 benchmarks/measure.py --workload <cell> [--workload ...] \\
        [--sets 2] [--runs 6] [--seconds N] [--traced 1] [--out DIR]

Each run is a new process of BENCHMARK.json's command, one at a time (this
process never touches JAX, so it never holds the chip). Run k of every set
uses the same seed, a large one. A spread is the distance between the first
and the third quartile (`statistics.quantiles(values, n=4)`) as a share of
the median; the bound the contract asks for is about five times the widest
spread over the cells. Every run's two last lines are appended to
<out>/<cell>.jsonl (default chiprun_out/measure).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED0 = 3_000_000_011       # over 2**31, as the driver's seeds are


def run_once(command: list[str], cell: str, seed: int, seconds: int,
             trace: int, out_dir: str) -> dict | None:
    proc = subprocess.run(
        command + ["--workload", cell, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    with open(os.path.join(out_dir, cell + ".jsonl"), "a",
              encoding="utf-8") as f:
        for line in lines[-2:]:
            f.write(line + "\n")
    if proc.returncode != 0 or not lines:
        print(f"  {cell} seed {seed}: rc {proc.returncode}\n"
              f"{proc.stderr[-2000:]}", flush=True)
        return None
    return json.loads(lines[-1])


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--traced", type=int, default=1,
                    help="traced runs to make after the sets")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "measure"))
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    bad = 0
    # set by set over all the cells, so that a call cut short still holds
    # a whole set of each
    sets = {cell: [{} for _ in range(args.sets)] for cell in args.workload}
    for s in range(args.sets):
        for cell in args.workload:
            values = sets[cell][s]
            for k in range(args.runs):
                res = run_once(bench["command"], cell, SEED0 + k, seconds,
                               0, args.out)
                if res is None or not res["correct"]:
                    bad += 1
                    continue
                for name, m in res["metrics"].items():
                    values.setdefault(name, []).append(m["value"])
                print(f"  {cell} set {s + 1} run {k + 1}: "
                      + " ".join(f"{n}={m['value']:.6g}"
                                 for n, m in res["metrics"].items())
                      + f" ops={res['attempted']} failed={res['failed']}",
                      flush=True)
    for cell in args.workload:
        for name in sets[cell][0]:
            runs = [v[name] for v in sets[cell] if len(v.get(name, ())) > 1]
            print(f"{cell} {name}: medians "
                  + " ".join(f"{statistics.median(v):.6g}" for v in runs)
                  + "  spreads "
                  + " ".join(f"{100 * spread(v):.2f}%" for v in runs),
                  flush=True)
        for k in range(args.traced):
            res = run_once(bench["command"], cell, SEED0 + 100 + k, seconds,
                           1, args.out)
            if res is None or not res["correct"]:
                bad += 1
                continue
            print(f"{cell} traced: " + json.dumps(res), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
