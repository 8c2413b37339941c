"""Measure solver tuning constants on the chip.

The CPU knobs (chains=1, 64 proposals) were pinned from a measured matrix;
the accelerator defaults (2 chains at the 256-proposal knee) come from one
partial sweep that predates PRs 1-20. This script runs the matrix on the
accelerator and fails without one (platform.init_platform,
require_accelerator; FLEET_FORCE_CPU=1 for an explicit CPU rehearsal): for
each config it compiles once (warm-up solve), then times REPS solves and
reports the median, for both the cold solve and the warm single-node-kill
reschedule.

Usage:  python scripts/tpu_tune.py [--small] [--reps 3]
The grid varies one axis at a time around the current default rather than
the full cross-product: each distinct (chains, block, proposals) shape pays
an XLA compile, and chip time is budgeted.

Output is JSON Lines on stdout (progress on stderr), one object per line,
each flushed the moment it is measured: a {"kind": "header"} line, then
{"kind": "cold"|"warm"} rows — a run cut short at its time limit keeps
every leg it finished.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

# runnable as `python scripts/tpu_tune.py` from the repo root: sys.path[0]
# is scripts/, so the package root must be added explicitly
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _median_ms(fn, reps: int) -> tuple[float, list[float], object]:
    times, last = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        last = fn()
        times.append((time.perf_counter() - t0) * 1e3)
    times_sorted = sorted(times)
    return times_sorted[(reps - 1) // 2], [round(t, 1) for t in times], last


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true", help="1k x 100 instance")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    from fleetflow_tpu.platform import init_platform
    device = init_platform(require_accelerator=True)
    S, N = (1000, 100) if args.small else (10000, 1000)
    print(f"[tune] device={device} instance={S}x{N}", file=sys.stderr,
          flush=True)

    import numpy as np

    from fleetflow_tpu.lower import synthetic_problem
    from fleetflow_tpu.solver import prepare_problem, solve

    pt = synthetic_problem(S, N, seed=0, n_tenants=8,
                           port_fraction=0.2, volume_fraction=0.1)
    prob = prepare_problem(pt)

    def emit(obj: dict) -> None:
        # one flushed line per measurement: a run killed at its time
        # limit after this point cannot lose it
        print(json.dumps(obj), flush=True)

    emit({"kind": "header", "device": device, "instance": [S, N],
          "reps": args.reps})

    def run_cold(chains: int, block: int, props: int):
        t_c = time.perf_counter()
        solve(pt, prob=prob, chains=chains, steps=128, seed=0,
              seed_batch=256, anneal_block=block, proposals_per_step=props)
        compile_s = time.perf_counter() - t_c
        med, times, res = _median_ms(
            lambda: solve(pt, prob=prob, chains=chains, steps=128, seed=1,
                          seed_batch=256, anneal_block=block,
                          proposals_per_step=props), args.reps)
        emit({"kind": "cold", "chains": chains, "block": block,
              "proposals": props, "median_ms": round(med, 1),
              "runs_ms": times, "compile_s": round(compile_s, 1),
              "violations": res.violations, "soft": round(res.soft, 4),
              "sweeps": int(res.steps)})
        print(f"[tune] cold chains={chains} block={block} props={props}: "
              f"{med:.1f} ms soft={res.soft:.4f} viol={res.violations} "
              f"(compile {compile_s:.0f}s)", file=sys.stderr, flush=True)
        return res

    # Ordered so the legs the r5 partial sweep never reached run FIRST:
    # pinned default as the warm-start reference, then the unmeasured block
    # axis, then the warm legs, then the r5 rows for cross-checking, and
    # the 512-proposal leg (the largest compile) last.
    ref = run_cold(2, 1, 256)      # pinned default (r5 winner + block=1)
    for chains, block, props in [(2, 2, 256), (2, 4, 256), (2, 8, 256)]:
        run_cold(chains, block, props)

    # warm reschedule: kill the most-loaded node, re-solve from the cold
    # reference (BASELINE.json config 5)
    victim = int(np.bincount(ref.assignment, minlength=N).argmax())
    valid = pt.node_valid.copy()
    valid[victim] = False
    pt2 = dataclasses.replace(pt, node_valid=valid)
    import jax.numpy as jnp
    prob2 = dataclasses.replace(prob, node_valid=jnp.asarray(valid))
    for chains, block, props in [(2, 1, 256), (1, 1, 256), (2, 2, 256),
                                 (1, 1, 64), (4, 1, 256)]:
        t_c = time.perf_counter()
        solve(pt2, prob=prob2, chains=chains, steps=128, seed=2,
              init_assignment=ref.assignment, anneal_block=8,
              warm_block=block, proposals_per_step=props)
        compile_s = time.perf_counter() - t_c
        med, times, res = _median_ms(
            lambda: solve(pt2, prob=prob2, chains=chains, steps=128, seed=3,
                          init_assignment=ref.assignment, anneal_block=8,
                          warm_block=block, proposals_per_step=props),
            args.reps)
        emit({"kind": "warm", "chains": chains, "warm_block": block,
              "proposals": props, "median_ms": round(med, 1),
              "runs_ms": times, "compile_s": round(compile_s, 1),
              "violations": res.violations, "soft": round(res.soft, 4),
              "sweeps": int(res.steps)})
        print(f"[tune] warm chains={chains} wblock={block} props={props}: "
              f"{med:.1f} ms soft={res.soft:.4f} viol={res.violations} "
              f"(compile {compile_s:.0f}s)", file=sys.stderr, flush=True)

    for chains, block, props in [(4, 8, 256), (1, 8, 256), (8, 8, 256),
                                 (4, 8, 128), (4, 8, 64), (1, 2, 64),
                                 (4, 8, 512)]:
        run_cold(chains, block, props)


if __name__ == "__main__":
    main()
