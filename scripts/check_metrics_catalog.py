#!/usr/bin/env python
"""CI gate: the guide/10 metric catalog and the registered metric
families must be the SAME set, both ways.

The exposition golden (scripts/check_metrics_endpoint.py) pins the
/metrics surface against tests/goldens/metrics_exposition.txt — but
nothing pinned the CATALOG TABLE in docs/guide/10-observability.md
against either, so families could ship documented-nowhere (operators
can't find them) or documented-but-deleted (dashboards reference
ghosts). This script closes the triangle:

  registered families (REGISTRY, full instrumented import surface)
      == documented families (the `| `fleet_...`` rows of guide/10)

Run as a tier-1 CI step; no golden to regenerate — the guide itself is
the golden. A new family lands with its catalog row in the same diff.
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

GUIDE = ROOT / "docs" / "guide" / "10-observability.md"

# first backticked fleet_* token of a catalog table row
_ROW = re.compile(r"^\|\s*`(fleet_[a-zA-Z0-9_]+)`")


def registered() -> set[str]:
    # the full instrumented surface (the check_metrics_endpoint import
    # set, plus the modules only reached lazily from it)
    import fleetflow_tpu.agent.agent        # noqa: F401
    import fleetflow_tpu.agent.monitor      # noqa: F401
    import fleetflow_tpu.chaos.simulate     # noqa: F401 (plan-simulate families)
    import fleetflow_tpu.chaos.worldgen     # noqa: F401 (world families)
    import fleetflow_tpu.cloud.provider     # noqa: F401
    import fleetflow_tpu.core.parsecache    # noqa: F401
    import fleetflow_tpu.cp.autoscaler      # noqa: F401
    import fleetflow_tpu.cp.handlers        # noqa: F401 (server loads lazily)
    import fleetflow_tpu.cp.server          # noqa: F401
    import fleetflow_tpu.obs.collector      # noqa: F401 (server loads lazily)
    import fleetflow_tpu.obs.slo            # noqa: F401
    import fleetflow_tpu.platform           # noqa: F401 (compile-cache gauge)
    import fleetflow_tpu.registry.aggregate  # noqa: F401
    import fleetflow_tpu.solver.api         # noqa: F401
    import fleetflow_tpu.solver.sharded     # noqa: F401
    import fleetflow_tpu.solver.subsolve    # noqa: F401
    from fleetflow_tpu.obs.metrics import REGISTRY
    return set(REGISTRY.names())


def documented() -> set[str]:
    names = set()
    for line in GUIDE.read_text().splitlines():
        m = _ROW.match(line)
        if m:
            names.add(m.group(1))
    return names


def main() -> int:
    reg = registered()
    doc = documented()
    errors = []
    for name in sorted(reg - doc):
        errors.append(f"registered but missing from the guide/10 "
                      f"catalog: {name}")
    for name in sorted(doc - reg):
        errors.append(f"documented in guide/10 but not registered "
                      f"anywhere: {name}")
    if errors:
        print("metrics catalog drift check FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print(f"metrics catalog in sync ({len(reg)} families)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
