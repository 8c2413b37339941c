"""Every cell runs end to end at its rehearsal size on the CPU, in a new
process as the driver starts it, and prints the contract's last line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 3_000_000_017        # over 2**31, as the driver's seeds are


def run_cell(cell, trace, *extra, root=ROOT, seconds="2"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", seconds,
         "--trace", str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def listed(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


def check_line(proc, cell, trace):
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) == keys | ({"breakdown"} if trace else set())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    device = result["device"]
    assert device["platform"] == "cpu" and device["count"] == 1
    assert {"kind", "memory_peak_bytes"} <= set(device)
    wanted = listed("per_layer" if trace else "end_to_end", cell)
    assert set(result["metrics"]) == wanted
    units = {m["name"]: m["unit"]
             for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and isinstance(m["value"], float)
    if trace:
        assert 0 < device["busy_s"] < device["window_s"]
        for rows in result["breakdown"].values():
            assert len(rows) <= 10
            assert all(len(name) <= 80 for name, _ in rows)
    return result


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    result = check_line(run_cell(cell, trace, "--cpu-rehearsal"), cell, trace)
    if trace:
        assert result["metrics"]["compile_events_in_window"]["value"] == 0


def test_without_a_chip_nothing_is_printed():
    proc = run_cell(CELLS[0], 0)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "--cpu-rehearsal" in proc.stderr


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A later PR's deployment and traffic mix: two new JSON files and two
    entries in BENCHMARK.json, no edit to a file that is there."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(tmp_path / "benchmarks" / "configs" / "tiny.json", "w",
              encoding="utf-8") as f:
        json.dump({"name": "tiny", "source": "test", "reduced": [],
                   "deployment": {"kind": "generated", "services": 400,
                                  "nodes": 40, "stage": "app0"}}, f)
    with open(tmp_path / "benchmarks" / "traffic" / "one-dead.json", "w",
              encoding="utf-8") as f:
        json.dump({"name": "one-dead", "op": "node_events",
                   "params": {"max_dead": 1}, "warmup": [{"ops": 3}],
                   "trace_seconds": 1, "min_traced_ops": 2}, f)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny.json",
                             "why": "test"})
    bench["workloads"].append({"name": "tiny.one-dead", "config": "tiny",
                               "traffic": "one-dead", "chips": 1,
                               "why": "test"})
    with open(tmp_path / "BENCHMARK.json", "w", encoding="utf-8") as f:
        json.dump(bench, f)
    proc = run_cell("tiny.one-dead", 0, "--cpu-rehearsal", root=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and set(result["metrics"]) == {"op_p50_ms",
                                                            "setup_s"}


UNLISTED = [
    # built, rehearsed, not yet proven on the chip (PERF.md §7, rows 0a, 0b)
    ("shop-live", "redeploy", {"op_p50_ms", "op_p95_ms", "setup_s"}),
    ("mt10kx1k", "registry-solve", {"op_p50_ms", "placed_per_s", "setup_s"}),
]


@pytest.mark.parametrize("config, traffic, metrics", UNLISTED)
def test_a_built_cell_not_yet_listed_rehearses(tmp_path, config, traffic,
                                               metrics):
    """The two cells whose files are here but which BENCHMARK.json does not
    list yet run once their entries are added — and only entries."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = f"{config}.{traffic}"
    bench = json.loads(json.dumps(BENCH))
    if config not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append({
            "name": config, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{config}.json"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    for name, unit in (("op_p95_ms", "ms"), ("placed_per_s", "services/s")):
        if name in metrics:
            bench["end_to_end"].append({
                "name": name, "unit": unit, "better": "lower", "bound": 0.1,
                "source": "host_clock", "workloads": [cell]})
    with open(tmp_path / "BENCHMARK.json", "w", encoding="utf-8") as f:
        json.dump(bench, f)
    proc = run_cell(cell, 0, "--cpu-rehearsal", root=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == metrics
