"""Op kind `solve_commit_preempt`: a batch of high-priority pods scheduled
and committed over the wire onto a cluster that low-priority pods have
filled, so that every pod of the batch evicts.

Set-up: CP in-process (`layers.ServedCp.start`), the deployment's nodes
registered online in its store, namespace sched-0 (the init pods, four a
node, no room for a fifth) solved and committed through
`PlacementService.solve_stage` + `commit` in-process — 20,000 pods are no
one frame — and checked; the reference schedules the same cluster once
(init pods, then one batch, preempting), to show the instance has an
answer and how many victims it forces. Op, timed from the first request
sent to the second reply in hand: `placement.solve` of stage sched-1 with
`reserve: true` — the measured pods under fresh names every op, as the
source's are new objects — then `placement.commit` of the reservation,
both over the one `ProtocolClient` connection. Between ops, in `prepare`
and outside the timed part, the cluster goes back to the init state by
the program's own calls: `PlacementService.release_stage("k8s/sched-1")`
returns the batch, `PlacementService.reinstate("k8s/sched-0")` puts the
victims back where they were (the source runs each measurement from the
init state).

`verify` holds the reply to `reference_k8s_preemption.check`, with BOTH
placement records and every touched server record read back from the
store: sched-1's record is the reply's assignment, sched-0's has lost
exactly the reply's victims, each touched server's `allocated` is the sum
of what remains on it. The op fails too if it was infeasible, not
committed, served by anything but the device annealer (a host fallback, a
relaxed rung), if the commit says it evicted another number than the
solve named, if that number differs from the reference's, or if it did
not start from the init state.
"""

from __future__ import annotations

from benchmarks import generators_k8s_preemption as generators
from benchmarks import layers
from benchmarks import reference_k8s_preemption as reference
from benchmarks.reference_k8s_preemption import INIT, MEASURED

# `allocated` is summed in float64 from float32 demands
ALLOCATED_RTOL = 1e-4


def stage_key(namespace: str) -> str:
    return f"{generators.FLOW}/{namespace}"


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
        from fleetflow_tpu.cp.models import ServerCapacity
        from fleetflow_tpu.cp.protocol import encode_frame

        cell = self.cell
        with cell.phase("generate"):
            self.model = generators.model(cell.config, cell.seed,
                                          cell.rehearsal)
            self.pods = {ns: {p["name"]: p for p in pods}
                         for ns, pods in self.model["namespaces"].items()}
            cell.notes["solve_request_bytes"] = len(encode_frame(
                {"type": "request", "id": 0, "channel": "placement",
                 "method": "solve",
                 "payload": generators.solve_request(self.model, MEASURED)}))
        with cell.phase("reference"):
            mine, victims = reference.schedule(self.model, {})
            self.forced_victims = sum(map(len, victims.values()))
            was = {INIT: {**mine[INIT], **victims.get(INIT, {})},
                   MEASURED: mine[MEASURED]}
            found = reference.check(self.model, was, victims)
            cell.notes["reference"] = {
                "placed": {ns: sum(v is not None for v in a.values())
                           for ns, a in was.items()},
                "victims": self.forced_victims, "check": found["total"]}
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
                        **generators.server_capacity(node)))
        with cell.phase("baseline_solve"):
            placement, rid = state.placement.solve_stage(
                generators.flow(self.model, INIT), INIT)
            faults = self._served_by(placement.source)
            if not placement.feasible:
                faults.append(f"infeasible: {placement.violations}")
            elif not state.placement.commit(rid):
                faults.append("not committed")
            self.init = dict(placement.assignment)
            found = reference.check(self.model, {INIT: self.init}, {})
            if faults or found["total"]:
                raise RuntimeError(f"init pods not placed: {faults} "
                                   f"{found}")

    def prepare(self, i: int) -> dict:
        placement = self.cp.state.placement
        placement.release_stage(stage_key(MEASURED))
        placement.reinstate(stage_key(INIT))
        rec = self._committed(INIT)
        model = reference.measured_batch(self.model, i)
        return {"model": model, "init_rows": len(rec.assignment),
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
        arrivals = {p["name"]: p for p in model["namespaces"][MEASURED]}
        faults = self._served_by(reply["source"])
        if not reply["feasible"]:
            faults.append(f"infeasible: {reply['violations']} violations")
        if prepared["init_rows"] != len(self.init):
            faults.append(f"started from {prepared['init_rows']} init "
                          f"pods, not {len(self.init)}")
        victims: dict[str, dict] = {}
        for v in reply.get("victims", ()):
            victims.setdefault(v["stage"].split("/", 1)[1],
                               {})[v["service"]] = v["server"]
        n_victims = sum(map(len, victims.values()))
        if n_victims != self.forced_victims:
            faults.append(f"{n_victims} victims, the reference "
                          f"{self.forced_victims}")
        if not done["ok"] or done.get("evicted") != n_victims:
            faults.append(f"commit replied {done}")
        # both records, read back
        mine, init = self._committed(MEASURED), self._committed(INIT)
        if mine is None or dict(mine.assignment) != reply["assignment"]:
            faults.append("placement not committed")
        survivors = dict(init.assignment) if init is not None else {}
        gone = victims.get(INIT, {})
        if (survivors.keys() != self.init.keys() - gone.keys()
                or any(self.init[n] != s for n, s in survivors.items())):
            faults.append("the init record did not lose exactly the victims")
        found = reference.check(
            model, {INIT: {**survivors, **gone},
                    MEASURED: reply["assignment"]}, victims)
        if found["total"]:
            faults.append(f"reference check: {found}")
        # every touched server: `allocated` is the sum of what remains
        want: dict[str, list] = {
            slug: [0.0, 0.0] for slug in reply["assignment"].values()}
        for pods, assignment in ((self.pods[INIT], survivors),
                                 (arrivals, reply["assignment"])):
            for name, slug in assignment.items():
                if slug in want:
                    want[slug][0] += pods[name]["cpu"]
                    want[slug][1] += pods[name]["memory"]
        store = self.cp.state.store
        off = 0
        for slug, (cpu, memory) in want.items():
            s = store.server_by_slug(slug)
            off += (abs(s.allocated.cpu - cpu) > ALLOCATED_RTOL * cpu
                    or abs(s.allocated.memory - memory)
                    > ALLOCATED_RTOL * memory)
        if off:
            faults.append(f"{off} touched servers whose allocated is not "
                          f"the sum of what remains")
        return len(arrivals), faults

    async def close(self) -> None:
        await self.cp.stop()
