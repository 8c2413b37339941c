"""`test_rehearsal.py::test_cell_rehearses` gives every listed cell one CPU
device and asks for every per-layer metric the cell lists. A cell of four
chips lists what only a mesh feeds, and one device is no mesh: that one
case is expected to fail for that reason and no other (strict), and
`test_pod_cell.py` rehearses the cell on one device and on four virtual
ones in its place. A `benchmark` issue that edits test_rehearsal.py takes
this file away (PERF.md §7)."""

import pytest

ONE_DEVICE_IS_NO_MESH = (
    "test_rehearsal.py::test_cell_rehearses[pod100kx1k.node-churn-moved-1]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(ONE_DEVICE_IS_NO_MESH):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="one CPU device cannot route to the "
                "mesh: sharded_delta_share, sharded_dispatch_ms_per_solve "
                "and tempering_swap_accept_share have nothing to read"))
