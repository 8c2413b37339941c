"""The cell `pod100kx1k.node-churn-moved`: it is files and entries only;
every per-layer metric it lists resolves to a file and a reader; its
rehearsal is correct end to end on the CPU — on one device (the
single-chip path: the mesh's three metrics have nothing to read there) and
on four virtual devices with the mesh forced, where every listed metric is
present; and its op kind refuses a program that has no reply form `moved`.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks.tests.test_rehearsal import (BENCH, ROOT, SEED, check_line,
                                             listed, run_cell)

CELL = "pod100kx1k.node-churn-moved"
# what only the mesh-sharded annealer feeds
MESH_ONLY = {"sharded_delta_share", "sharded_dispatch_ms_per_solve",
             "tempering_swap_accept_share"}
with open(os.path.join(ROOT, "benchmarks", "configs", "pod100kx1k.json"),
          encoding="utf-8") as f:
    CONFIG = json.load(f)


def last_lines(proc):
    info, result = proc.stdout.strip().splitlines()[-2:]
    return json.loads(info)["info"], json.loads(result)


def test_the_cell_is_files_and_entries():
    config = next(c for c in BENCH["configs"] if c["name"] == "pod100kx1k")
    assert config["reduced"] == [] == CONFIG["reduced"]
    assert config["source"] == CONFIG["source"]
    assert 1 <= len(config["source"]) <= 200
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and len(cell["why"]) <= 200
    dep = CONFIG["deployment"]
    # every 20th service has two replicas: exactly 100,000 rows
    rows = dep["services"] + len(range(10, dep["services"], 20))
    assert (rows, dep["nodes"]) == (100_000, 1000)
    from fleetflow_tpu.solver.sharded import SHARDED_MIN_CELLS
    assert rows * dep["nodes"] >= SHARDED_MIN_CELLS
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "node-churn-moved.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    assert traffic["op"] == "node_events_moved"
    assert traffic["params"] == {"max_dead": 2}
    assert [(s["ops"], s.get("env")) for s in traffic["warmup"]] \
        == [(4, None)]


@pytest.mark.parametrize("metric", sorted(listed("per_layer", CELL)))
def test_a_listed_metric_resolves_to_a_file_and_a_reader(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           metric + ".json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert (spec["unit"], spec["layer"], spec["moves"]) \
        == (entry["unit"], entry["layer"], entry["moves"])
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)


def test_the_cell_rehearses_on_one_device():
    """The single-chip path: correct, and every listed metric but the
    mesh's own is there."""
    proc = run_cell(CELL, 1, "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    info, result = last_lines(proc)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == listed("per_layer", CELL) - MESH_ONLY
    assert info["notes"]["mesh_check"].startswith("skipped")
    assert info["compile_in_window"]["events"] == 0
    assert 0 < result["metrics"]["reply_rows_per_op"]["value"] < 100
    check_line(run_cell(CELL, 0, "--cpu-rehearsal"), CELL, 0)


def test_the_cell_rehearses_on_the_mesh():
    """Four virtual CPU devices and the route forced: the mesh under the
    CP under the harness, every listed metric present."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               FLEET_SHARDED="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "3",
         "--trace", "1", "--cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    info, result = last_lines(proc)
    assert result["device"]["count"] == 4
    assert result["correct"] and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == listed("per_layer", CELL)
    assert metrics["sharded_delta_share"] == 100
    assert metrics["resident_delta_share"] == 100
    assert metrics["host_transfers_per_op"] == 0
    assert metrics["sweeps_per_solve"] > 0
    assert 0 <= metrics["tempering_swap_accept_share"] <= 100
    assert info["compile_in_window"]["events"] == 0


def test_the_op_kind_refuses_a_program_without_the_form():
    """A parent whose handlers export no reply forms: the import of the op
    kind fails, before any set-up."""
    code = (
        "import fleetflow_tpu.cp.handlers as h\n"
        "del h.NODE_EVENTS_REPLY_FORMS\n"
        "import benchmarks.ops.node_events_moved\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   PYTHONPATH=ROOT),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "NODE_EVENTS_REPLY_FORMS" in proc.stderr
