"""The six metrics of the agents' side of rd's fan-out: their files, their
entries (appended after the admission victims' metric, each listing rd
alone), a program whose ring holds none of their phases (nothing is read),
and a CPU rehearsal of rd in which all six read a value."""

import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import program_span, span_tree          # noqa: E402
from benchmarks.tests.test_rehearsal import run_cell             # noqa: E402

RD = "shop-live.redeploy"
# name -> (reader, phases, part, layer)
NEW = {
    "agent_command_ms_per_op": ("program_span", ["agent.command"], None,
                                "agent fan-out"),
    "agent_parse_ms_per_op": ("program_span", ["agent.command.parse"], None,
                              "agent fan-out"),
    "agent_executor_wait_ms_per_op": ("program_span", ["agent.wait.executor"],
                                      None, "agent fan-out"),
    "agent_deploy_ms_per_op": ("program_span", ["agent.deploy"], None,
                               "agent fan-out"),
    "fanout_self_ms_per_op": ("span_tree", ["agents.send_batch"], "self",
                              "agent fan-out"),
    "event_wait_ms_per_op": ("program_span", ["protocol.wait.event"], None,
                             "protocol and client"),
}
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name", list(NEW))
def test_file_and_entry_agree(name):
    reader, spans, part, layer = NEW[name]
    [entry] = [m for m in BENCH["per_layer"] if m["name"] == name]
    spec = _spec(name)
    assert spec["name"] == name and spec["reader"] == reader
    assert spec["params"]["spans"] == spans
    assert spec["params"].get("part") == part
    assert spec["params"]["per"] == "ops"
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "op_p50_ms", "workloads": [RD]}
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        "ms", layer, "op_p50_ms")


def test_the_entries_are_appended_at_the_end():
    names = [m["name"] for m in BENCH["per_layer"]]
    i = names.index("admit_victims_ms_per_solve") + 1
    assert names[i:i + len(NEW)] == list(NEW)
    layers = {m["layer"] for m in BENCH["per_layer"][:i]}
    assert {"agent fan-out", "protocol and client"} <= layers


def test_a_program_without_the_phases_reads_nothing():
    """A window in which none of the phases closed — the parent's tree,
    where the agents open none of them — gives no value, and no 0."""
    t0 = time.perf_counter()
    t1 = t0 + 1e-6
    run = types.SimpleNamespace(
        spans=types.SimpleNamespace(events=[("op", t0, t1)]),
        count=lambda what: 1)
    for name in NEW:
        reader = {"program_span": program_span,
                  "span_tree": span_tree}[NEW[name][0]]
        assert reader.read(_spec(name)["params"], run) is None


def test_all_six_read_a_value_in_a_rehearsal_of_rd():
    proc = run_cell(RD, 1, "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in NEW:
        assert metrics[name]["value"] > 0, name
    # the self time is a part of the fan-out the outside wrapper times
    assert metrics["fanout_self_ms_per_op"]["value"] \
        < metrics["agent_fanout_ms_per_op"]["value"]
    # the agents' parse, hops and engine are parts of their commands
    parts = sum(metrics[n]["value"] for n in (
        "agent_parse_ms_per_op", "agent_executor_wait_ms_per_op",
        "agent_deploy_ms_per_op"))
    assert parts < metrics["agent_command_ms_per_op"]["value"]
