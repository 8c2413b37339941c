"""Three tests of the benchmark's own read `BENCHMARK.json` as the PR that
wrote them left it, and a later PR may edit no file the benchmark has
(`benchmarks/conftest.py`, which does this for PR 37's pinning test, and
`benchmarks/tests/conftest.py` are two of them, which leaves this
directory). Only those tests are touched, once, when they are collected; a
run that does not collect them (tier-1 is `tests/`) finds nothing to do.

- `benchmarks/tests/test_spread_cell.py::test_the_new_metrics_are_files_and_the_last_entries`
  (PR 39) says its five entries were appended to `per_layer` by asserting
  that they are the list's LAST five, and that each lists the cell
  `k8s-sp-topology-spread-5k.spread-pods` and no other. A later PR
  appends its entries at the end too, as every PR has to, and a later
  cell joins `relaxed_rungs_per_op` (`k8s-sp-preemption-5k-admit
  .admit-preempt-pods`, PR 48): so that test reads `per_layer` as far as
  PR 39's last entry, and those five lists as far as PR 39's cell, and
  every assertion it makes about them stays live. What a later PR appends
  is its own test's to check (`benchmarks/tests/test_basic_cell.py` for
  PR 41's, by position and not by "last").
- `benchmarks/tests/test_basic_cell.py::test_the_new_metrics_are_files_and_appended_entries`
  (PR 41) says its eleven `admission_*` / `admit_*` metrics were its own
  by asserting that each lists the cell `k8s-sp-basic-5k.admit-pods` and
  no other. A later cell through admission joins those lists, as cells
  join the lists of metrics their path reports
  (`k8s-sp-antiaffinity-5k-admit.admit-anti-pods`, PR 45): so that test
  reads each of the eleven lists as far as PR 41's cell.
- `benchmarks/tests/test_anti_admit_cell.py::test_the_entries_are_appended`
  (PR 45) says its configuration and its cell were appended by asserting
  that they are the LAST of `configs` and `workloads`, and that the lists
  it joined end in its cell. A later PR appends its configuration and
  cell after them and its cell to lists PR 45's joined (PR 48): so that
  test reads `configs` and `workloads` as far as PR 45's entries, and
  every metric's list as far as PR 45's cell.

A `benchmark` issue that makes the pinning tests say
`entries[i:i + len(NEW)] == NEW` and `CELL in workloads` takes this file
and `benchmarks/conftest.py` away (PERF.md §7p)."""

import functools

PINNED = {
    "test_spread_cell.py::test_the_new_metrics_are_files_and_the_last_entries":
        ("per_layer", "workloads"),
    "test_basic_cell.py::test_the_new_metrics_are_files_and_appended_entries":
        ("workloads",),
    "test_anti_admit_cell.py::test_the_entries_are_appended":
        ("entries",),
}


def pytest_collection_modifyitems(items):
    for item in items:
        for nodeid, cuts in PINNED.items():
            if item.nodeid.endswith(nodeid):
                item.obj = _as_left(item.obj, item.module, cuts)


def _left_per_layer(mod, whole: dict) -> dict:
    """`per_layer` as far as the module's last NEW entry."""
    names = [m["name"] for m in whole["per_layer"]]
    end = names.index(mod.NEW[-1]) + 1
    return {**whole, "per_layer": whole["per_layer"][:end]}


def _cut_at_cell(mod, m: dict, only_new: bool) -> dict:
    """Metric `m` with its `workloads` list as far as the module's CELL:
    of the module's NEW entries only, where `only_new`."""
    w = m.get("workloads")
    if (only_new and m["name"] not in mod.NEW) or w is None \
            or mod.CELL not in w:
        return m
    return {**m, "workloads": w[:w.index(mod.CELL) + 1]}


def _left_workloads(mod, whole: dict) -> dict:
    """The `workloads` lists of the module's NEW entries as far as its
    CELL."""
    return {**whole, "per_layer": [_cut_at_cell(mod, m, True)
                                   for m in whole["per_layer"]]}


def _left_entries(mod, whole: dict) -> dict:
    """`configs` and `workloads` as far as the module's own entries, and
    every metric's list as far as its CELL."""
    configs = [c["name"] for c in whole["configs"]]
    cells = [w["name"] for w in whole["workloads"]]
    return {**whole,
            "configs": whole["configs"][
                :configs.index(mod.CONFIG["name"]) + 1],
            "workloads": whole["workloads"][:cells.index(mod.CELL) + 1],
            "per_layer": [_cut_at_cell(mod, m, False)
                          for m in whole["per_layer"]]}


CUTS = {"per_layer": _left_per_layer, "workloads": _left_workloads,
        "entries": _left_entries}


def _as_left(test, mod, cuts: tuple):
    @functools.wraps(test)
    def pinned():
        whole = mod.BENCH
        left = whole
        for cut in cuts:
            left = CUTS[cut](mod, left)
        mod.BENCH = left
        try:
            test()
        finally:
            mod.BENCH = whole
    return pinned
