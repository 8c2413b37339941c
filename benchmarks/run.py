#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on the chip.

    python3 benchmarks/run.py --workload <config>.<traffic> --seed N \\
        --seconds S --trace 0|1 [--cpu-rehearsal]

A cell is an entry of `workloads` in BENCHMARK.json. Everything that
belongs to it is found by name: `configs/<config>.json`,
`traffic/<traffic>.json` (which names the op kind, `ops/<kind>.py`) and, for
each per-layer metric BENCHMARK.json gives the cell,
`layer_metrics/<metric>.json` (which names its reader, `readers/<reader>.py`).

The run: set-up (platform, deployment from --seed, the program's own
objects, baseline), the traffic's warm-up ops, then a closed loop of one
client for --seconds. An op is timed from the request being sent to the
reply in hand (and committed, where the path commits); the checker runs on
every reply, outside the timed part. With --trace 1 the same loop runs, the
profiler covers its first seconds, and the per-layer metrics are printed
instead of the end-to-end ones.

Without a TPU the run exits 1 and prints no result, unless --cpu-rehearsal
is given: that runs the configuration's `rehearsal` sizes on the CPU and
says so in `device`. The last line of stdout is the result; set-up phases,
compile events and faults are on the line before it.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse
import asyncio
import contextlib
import dataclasses
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import layers, trace_reduce          # noqa: E402
from benchmarks.spans import PREFIX, Spans, Watch    # noqa: E402

MAX_FAULTS_SHOWN = 5


def process_start() -> float:
    """The `time.perf_counter()` reading at which this process started."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """What an op kind is given: the cell's data, the seed, and where to
    record spans, phases and notes."""
    name: str
    config: dict
    traffic: dict
    seed: int
    rehearsal: bool
    device: dict
    spans: Spans
    phases: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.perf_counter() - t0)


@dataclasses.dataclass
class OpRecord:
    t0: float
    t1: float
    placed: int
    faults: list


@dataclasses.dataclass
class RunData:
    """What a per-layer reader is given."""
    spans: Spans
    counters: dict          # registry counter deltas over the window
    histograms: dict        # registry histogram (sum, count) deltas
    compile_setup: dict     # compile-or-load events before the window
    compile_window: dict    # ... and inside it
    ops: int
    trace: dict | None

    def count(self, what: str) -> int:
        if what == "ops":
            return self.ops
        if what == "solves":
            return self.spans.total("sched")[1]
        raise ValueError(f"unknown divisor {what!r}")


@contextlib.contextmanager
def environment(values: dict):
    before = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def init_device(rehearsal: bool, chips: int) -> dict:
    from fleetflow_tpu.platform import force_cpu, init_platform
    if rehearsal:
        force_cpu(1)
    device = init_platform()
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] < chips):
        print(f"benchmarks/run.py: the cell needs {chips} TPU chip(s) and "
              f"JAX found {device}; nothing was run. --cpu-rehearsal is the "
              f"sandbox rehearsal.", file=sys.stderr)
        raise SystemExit(1)
    return device


async def one_op(op, cell: Cell, i: int) -> OpRecord:
    prepared = op.prepare(i)
    t0 = time.perf_counter()
    with cell.spans.span("op"):
        try:
            result, error = await op.request(prepared), None
        except Exception as e:      # the loop must go on; the op is failed
            result, error = None, e
    t1 = time.perf_counter()
    if error is not None:
        return OpRecord(t0, t1, 0, [f"raised {error!r}"])
    with cell.phase("check"):
        placed, faults = op.verify(prepared, result)
    return OpRecord(t0, t1, 0 if faults else placed, faults)


class Profiler:
    """The profiler over the first part of the window."""

    def __init__(self, keep_dir: str | None):
        import jax.profiler
        self.keep = keep_dir is not None
        self.dir = keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
        # the Python tracer slows host code several times over and the
        # host is what this system waits for: benchmark spans and device
        # events are all the reduction reads
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.window = jax.profiler.TraceAnnotation(PREFIX + "trace_window")
        self.window.__enter__()
        self.on = True

    def stop(self, platform: str) -> dict:
        import jax.profiler
        self.window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
        try:
            files = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))
            if not files:
                raise trace_reduce.TraceError(
                    f"the profiler wrote no trace under {self.dir}")
            return trace_reduce.reduce_trace(files[-1], platform)
        finally:
            if not self.keep:
                shutil.rmtree(self.dir, ignore_errors=True)


def percentile(values: list[float], q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values), q))


def end_to_end(records: list[OpRecord], window_s: float,
               setup_s: float) -> dict[str, float]:
    lat = [(r.t1 - r.t0) * 1e3 for r in records]
    return {"op_p50_ms": percentile(lat, 50),
            "op_p95_ms": percentile(lat, 95),
            "placed_per_s": sum(r.placed for r in records) / window_s,
            "setup_s": setup_s}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


async def run_cell(args, bench: dict, cell: Cell, t_start: float) -> int:
    watch = Watch()
    layers.wrap_scheduler(cell.spans)
    op = importlib.import_module(
        f"benchmarks.ops.{cell.traffic['op']}").Op(cell)
    await op.setup()
    try:
        n = 0
        with cell.phase("warmup"):
            for step in cell.traffic["warmup"]:
                with environment(step.get("env", {})):
                    for _ in range(step["ops"]):
                        rec = await one_op(op, cell, n)
                        n += 1
                        if rec.faults:
                            print(f"benchmarks/run.py: warm-up op {n} "
                                  f"failed: {rec.faults}", file=sys.stderr)
                            return 1

        compile_setup = watch.compile_stats()
        counters0, histograms0 = Watch.counters(), Watch.histograms()
        check_s0 = cell.phases.pop("check", 0.0)
        cell.spans.reset()
        profiler = (Profiler(args.trace_dir) if args.trace else None)
        trace = None
        records: list[OpRecord] = []
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        while time.perf_counter() - t0 < args.seconds:
            records.append(await one_op(op, cell, n))
            n += 1
            if (profiler is not None and profiler.on
                    and time.perf_counter() - t0
                    >= cell.traffic["trace_seconds"]
                    and len(records) >= cell.traffic["min_traced_ops"]):
                trace = profiler.stop(cell.device["platform"])
        if profiler is not None and profiler.on:
            trace = profiler.stop(cell.device["platform"])
        window_s = records[-1].t1 - t0
        compile_all = watch.compile_stats()
        counters1, histograms1 = Watch.counters(), Watch.histograms()
    finally:
        await op.close()

    run = RunData(
        spans=cell.spans,
        counters={k: v - counters0.get(k, 0.0)
                  for k, v in counters1.items()},
        histograms={k: (v[0] - histograms0.get(k, (0.0, 0))[0],
                        v[1] - histograms0.get(k, (0.0, 0))[1])
                    for k, v in histograms1.items()},
        compile_setup=compile_setup,
        compile_window={k: compile_all[k] - compile_setup[k]
                        for k in compile_all},
        ops=len(records), trace=trace)

    metrics: dict[str, dict] = {}
    if args.trace:
        for entry in bench["per_layer"]:
            if not applies(entry, cell.name):
                continue
            spec = load_json(HERE, "layer_metrics", entry["name"] + ".json")
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(spec.get("params", {}), run)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    else:
        values = end_to_end(records, window_s, setup_s)
        for entry in bench["end_to_end"]:
            if applies(entry, cell.name):
                metrics[entry["name"]] = {"value": values[entry["name"]],
                                          "unit": entry["unit"]}

    failed = [r for r in records if r.faults]
    device = dict(cell.device, memory_peak_bytes=memory_peak_bytes())
    result = {"correct": bool(records) and not failed,
              "attempted": len(records), "failed": len(failed),
              "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    lat = sorted((r.t1 - r.t0) * 1e3 for r in records)
    print(json.dumps({"info": {
        "workload": cell.name, "seed": cell.seed, "seconds": args.seconds,
        "window_s": window_s, "setup_s": setup_s,
        "setup_phases_s": {k: round(v, 3) for k, v in cell.phases.items()
                           if k != "check"},
        "check_s_in_window": round(cell.phases.get("check", 0.0), 3),
        "check_s_in_warmup": round(check_s0, 3),
        "op_ms": {"min": lat[0], "p50": percentile(lat, 50),
                  "max": lat[-1]},
        "compile_setup": compile_setup,
        "compile_in_window": run.compile_window,
        "traced_ops": trace["ops"] if trace else None,
        "notes": cell.notes,
        "faults": [r.faults for r in failed[:MAX_FAULTS_SHOWN]]}}),
        flush=True)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="sandbox rehearsal: the configuration's tiny "
                         "sizes on the CPU; proves nothing about the chip")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace in this directory")
    args = ap.parse_args(argv)
    t_start = process_start()

    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        print(f"benchmarks/run.py: no workload {args.workload!r} in "
              f"BENCHMARK.json", file=sys.stderr)
        return 2
    config_file = next(c["file"] for c in bench["configs"]
                       if c["name"] == entry["config"])
    t_init = time.perf_counter()
    device = init_device(args.cpu_rehearsal, entry["chips"])
    cell = Cell(name=entry["name"], config=load_json(ROOT, config_file),
                traffic=load_json(HERE, "traffic",
                                  entry["traffic"] + ".json"),
                seed=args.seed, rehearsal=args.cpu_rehearsal,
                device=device, spans=Spans())
    cell.phases["imports"] = t_init - t_start
    cell.phases["platform_init"] = time.perf_counter() - t_init
    return asyncio.run(run_cell(args, bench, cell, t_start))


if __name__ == "__main__":
    sys.exit(main())
