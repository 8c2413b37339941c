"""Op kind `solve_commit_spread`: a batch of pods that must spread evenly
over the cluster's zones, scheduled and committed over the wire beside
another namespace's pods that are already running.

Set-up: CP in-process (`layers.ServedCp.start`), the deployment's nodes
registered online in its store, each record carrying its zone label;
namespace sched-0 (the init pods, no constraint) solved and committed
through `PlacementService.solve_stage` + `commit` in-process, as the source
schedules them without measuring, and checked; the reference schedules the
same cluster once, to show the instance has an answer and which zone counts
it ends in. Op, timed from the first request sent to the second reply in
hand: `placement.solve` of stage sched-1 with `reserve: true` — the
measured pods under fresh names every op, as the source's are new objects —
then `placement.commit` of the reservation, both over the one
`ProtocolClient` connection. Between ops, in `prepare` and outside the
timed part, the previous op's sched-1 is torn down
(`PlacementService.release_stage` with `forget`, what the CP's `down` of a
whole stage does): its commitment returned, its retained problem and
solver slot dropped. The source runs each measurement from the init state,
and the init state holds no placement of pods that no longer exist: every
op is a first solve of sched-1, from the seed. sched-0 stays.

`verify` holds the reply, taken together with the sched-0 placement read
back from the store, to `reference_k8s_spread.check` — capacity, pod count,
no pod on a node without the zone label, the zones' counts within maxSkew —
and the sorted zone counts to the reference's own. The op fails too if it
was infeasible, not committed, or served by anything but the device
annealer un-relaxed: a host fallback, a relaxed rung
(`+relaxed:spread` in `source`, or `fleet_sched_relaxed_total` moved during
the op), or a solve the host's repair had to finish
(`fleet_solver_spread_repair_moves_total` moved during the op).
"""

from __future__ import annotations

import inspect

from benchmarks import generators_k8s_spread as generators
from benchmarks import layers
from benchmarks import reference_k8s_spread as reference
from benchmarks.reference_k8s_spread import INIT, MEASURED
from benchmarks.spans import Watch, counter_sum

# what the host did in the annealer's place, if it moved during an op
HOST_DID_IT = ("fleet_solver_spread_repair_moves_total",
               "fleet_sched_relaxed_total")


def stage_key(namespace: str) -> str:
    return f"{generators.FLOW}/{namespace}"


def host_did_it() -> dict[str, float]:
    values = Watch.counters()
    return {name: counter_sum(values, name) for name in HOST_DID_IT}


class Op:
    def __init__(self, cell):
        self.cell = cell

    def _committed(self, namespace: str):
        key = stage_key(namespace)
        return self.cp.state.store.find_one(
            "placements", lambda p: p.stage_key == key)

    def _served_by(self, source: str) -> list[str]:
        wanted = f"{self.cell.device['platform']}-anneal"
        return ([] if source == wanted
                else [f"served by {source!r}, not {wanted!r}"])

    async def setup(self) -> None:
        from fleetflow_tpu.cp.models import ServerCapacity, ServerLabelsRec
        from fleetflow_tpu.cp.protocol import encode_frame

        cell = self.cell
        with cell.phase("generate"):
            self.model = generators.model(cell.config, cell.seed,
                                          cell.rehearsal)
            cell.notes["solve_request_bytes"] = len(encode_frame(
                {"type": "request", "id": 0, "channel": "placement",
                 "method": "solve",
                 "payload": generators.solve_request(self.model, MEASURED)}))
        with cell.phase("reference"):
            mine = reference.schedule(self.model, {})
            found = reference.check(self.model, mine)
            self.zone_counts = sorted(found["zones"].values())
            cell.notes["reference"] = {
                "placed": {ns: sum(v is not None for v in a.values())
                           for ns, a in mine.items()},
                "zones": self.zone_counts, "check": found["total"]}
            if found["total"]:
                raise RuntimeError(f"the reference cannot place the "
                                   f"cluster: {found}")
        with cell.phase("cp_start"):
            self.cp = await layers.ServedCp.start(cell.spans)
        state = self.cp.state
        with cell.phase("register_servers"):
            for slug, node in self.model["nodes"].items():
                rec = state.store.register_server(slug, tenant="default",
                                                  hostname=slug)
                state.store.update(
                    "servers", rec.id, status="online",
                    capacity=ServerCapacity(
                        **generators.server_capacity(node)),
                    labels=ServerLabelsRec(
                        **generators.server_labels(node)))
        with cell.phase("baseline_solve"):
            placement, rid = state.placement.solve_stage(
                generators.flow(self.model, INIT), INIT)
            faults = self._served_by(placement.source)
            if not placement.feasible:
                faults.append(f"infeasible: {placement.violations}")
            elif not state.placement.commit(rid):
                faults.append("not committed")
            self.init = dict(placement.assignment)
            found = reference.check(self.model, {INIT: self.init})
            if faults or found["total"]:
                raise RuntimeError(f"init pods not placed: {faults} "
                                   f"{found}")

    def _tear_down(self, key: str) -> None:
        placement = self.cp.state.placement
        if "forget" in inspect.signature(placement.release_stage).parameters:
            placement.release_stage(key, forget=True)
            return
        # a program from before `forget` (the parent this cell is first
        # compared with): the same end by hand, so that both sides solve
        # cold
        placement.release_stage(key)
        with placement._locked():
            placement._last.pop(key, None)

    def prepare(self, i: int) -> dict:
        self._tear_down(stage_key(MEASURED))
        model = reference.measured_batch(self.model, i)
        return {"model": model, "host_did_it": host_did_it(),
                "request": generators.solve_request(model, MEASURED)}

    async def request(self, prepared: dict):
        reply = await self.cp.conn.request("placement", "solve",
                                           prepared["request"], timeout=600)
        done = await self.cp.conn.request(
            "placement", "commit", {"reservation": reply["reservation"]},
            timeout=600)
        return reply, done

    def verify(self, prepared: dict, result) -> tuple[int, list[str]]:
        reply, done = result
        model = prepared["model"]
        faults = self._served_by(reply["source"])
        if not reply["feasible"]:
            faults.append(f"infeasible: {reply['violations']} violations")
        for name, after in host_did_it().items():
            moved = after - prepared["host_did_it"][name]
            if moved:
                faults.append(f"{name} moved by {moved:g} during the op")
        mine, init = self._committed(MEASURED), self._committed(INIT)
        if (not done["ok"] or mine is None
                or dict(mine.assignment) != reply["assignment"]):
            faults.append("placement not committed")
        held = dict(init.assignment) if init is not None else {}
        if held != self.init:
            faults.append("the init pods are not where set-up left them")
        found = reference.check(model, {INIT: held,
                                        MEASURED: reply["assignment"]})
        if found["total"]:
            faults.append(f"reference check: {found}")
        if sorted(found["zones"].values()) != self.zone_counts:
            faults.append(f"zone counts {found['zones']}, the reference "
                          f"{self.zone_counts}")
        return len(model["namespaces"][MEASURED]), faults

    async def close(self) -> None:
        await self.cp.stop()
