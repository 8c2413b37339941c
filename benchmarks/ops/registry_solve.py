"""Op kind `registry_solve`: what `fleet cp registry solve` does
(`cli/main.py _cmd_cp_registry`), called in-process — the cold path.

Op: the fleets' KDL texts -> `aggregate_fleets` (parse, namespace, lower)
-> `place_with_fallback(pick_scheduler(S, N), pt)` from scratch: a new
scheduler every op, so nothing resident is reused. There is no CP and no
commit on this path; the op ends when the placement is in hand. Each op's
texts differ from the previous op's by one comment line, so content-keyed
caches (core/parsecache.py, the registry's flow cache) miss as they do
after an operator's edit, and every shape stays the same. The server pool
is parsed once in set-up: the edit is to the fleets. Stages are named to
`aggregate_fleets` as a registry with routes names them.
"""

from __future__ import annotations

from benchmarks import checker, generators


class Op:
    def __init__(self, cell):
        self.cell = cell
        self.edits = 0

    async def setup(self) -> None:
        from fleetflow_tpu.core.parser import parse_kdl_string
        from fleetflow_tpu.registry.model import FleetEntry, Registry

        cell = self.cell
        with cell.phase("generate"):
            shape = dict(cell.config["registry"])
            if cell.rehearsal:
                shape.update(cell.config.get("rehearsal", {})
                             .get("registry", {}))
            self.texts, pool_text, model = generators.registry(
                shape["fleets"], shape["services_per_fleet"],
                shape["nodes"], cell.seed)
            self.model = checker.Model(**model)
            pool = parse_kdl_string(pool_text)
            self.registry = Registry(
                fleets={n: FleetEntry(name=n, path=n) for n in self.texts},
                servers=pool.servers)

    def prepare(self, i: int) -> dict[str, str]:
        self.edits += 1
        mark = f"// edit {self.cell.seed}.{self.edits}\n"
        return {n: mark + text for n, text in self.texts.items()}

    async def request(self, texts: dict[str, str]):
        from fleetflow_tpu.core.parser import parse_kdl_string
        from fleetflow_tpu.registry.aggregate import aggregate_fleets
        from fleetflow_tpu.sched import pick_scheduler, place_with_fallback

        with self.cell.spans.span("frontend"):
            pt, _ = aggregate_fleets(
                self.registry, stages={n: ["prod"] for n in texts},
                loader=lambda path, stage: parse_kdl_string(texts[path]))
        return place_with_fallback(pick_scheduler(pt.S, pt.N), pt)

    def verify(self, texts, result) -> tuple[int, list[str]]:
        placement, relaxed = result
        faults = []
        if not placement.feasible:
            faults.append(f"infeasible: {placement.violations} violations")
        wanted = f"{self.cell.device['platform']}-anneal"
        if placement.source != wanted or relaxed:
            faults.append(f"served by {placement.source!r}, not {wanted!r}")
        found = checker.check(self.model, placement.assignment)
        if found["total"]:
            faults.append(f"checker: {found}")
        return len(self.model.rows), faults

    async def close(self) -> None:
        pass
