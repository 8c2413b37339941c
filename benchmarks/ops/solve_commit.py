"""Op kind `solve_commit`: a batch of new pods scheduled and committed over
the wire, beside another namespace's pods that are already running.

Set-up: CP in-process (`layers.ServedCp.start`), the deployment's nodes
registered online in its store, namespace sched-0 (the init pods) solved
and committed over the wire and checked; the reference schedules the same
cluster once, to show the instance has an answer. Op, timed from the first
request sent to the second reply in hand: `placement.solve` of stage
sched-1 with `reserve: true` — the measured pods under fresh names every
op, as the source's are new objects — then `placement.commit` of the
reservation, both over the one `ProtocolClient` connection. Between ops,
in `prepare` and outside the timed part, the previous op's sched-1
commitment is returned (`PlacementService.release_stage`): the source runs
each measurement from the init state; sched-0 stays.

`verify` holds the reply, taken together with the sched-0 placement read
back from the store, to `reference_k8s.check`; the op fails too if it was
infeasible, not committed, or served by anything but the device annealer
(a host fallback, a relaxed rung).
"""

from __future__ import annotations

from benchmarks import generators_k8s, layers, reference_k8s
from benchmarks.reference_k8s import INIT, MEASURED


class Op:
    def __init__(self, cell):
        self.cell = cell

    def _committed(self, namespace: str):
        key = f"{generators_k8s.FLOW}/{namespace}"
        return self.cp.state.store.find_one(
            "placements", lambda p: p.stage_key == key)

    async def _solve_commit(self, request: dict):
        reply = await self.cp.conn.request("placement", "solve", request,
                                           timeout=600)
        done = await self.cp.conn.request(
            "placement", "commit", {"reservation": reply["reservation"]},
            timeout=600)
        return reply, done

    def _faults(self, namespace: str, result) -> list[str]:
        reply, done = result
        faults = []
        if not reply["feasible"]:
            faults.append(f"infeasible: {reply['violations']} violations")
        wanted = f"{self.cell.device['platform']}-anneal"
        if reply["source"] != wanted:
            faults.append(f"served by {reply['source']!r}, not {wanted!r}")
        rec = self._committed(namespace)
        if (not done["ok"] or rec is None
                or dict(rec.assignment) != reply["assignment"]):
            faults.append("placement not committed")
        return faults

    async def setup(self) -> None:
        from fleetflow_tpu.cp.models import ServerCapacity
        from fleetflow_tpu.cp.protocol import encode_frame

        cell = self.cell
        with cell.phase("generate"):
            self.model = generators_k8s.model(cell.config, cell.seed,
                                              cell.rehearsal)
            init_request = generators_k8s.solve_request(self.model, INIT)
            cell.notes["solve_request_bytes"] = len(encode_frame(
                {"type": "request", "id": 0, "channel": "placement",
                 "method": "solve", "payload": init_request}))
        with cell.phase("reference"):
            mine = reference_k8s.schedule(self.model, {})
            found = reference_k8s.check(self.model, mine)
            cell.notes["reference"] = {
                "placed": {ns: sum(v is not None for v in a.values())
                           for ns, a in mine.items()},
                "check": found["total"]}
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
                        **generators_k8s.server_capacity(node)))
        with cell.phase("baseline_solve"):
            result = await self._solve_commit(init_request)
            faults = self._faults(INIT, result)
            found = reference_k8s.check(self.model,
                                        {INIT: result[0]["assignment"]})
            if faults or found["total"]:
                raise RuntimeError(f"init pods not placed: {faults} "
                                   f"{found}")

    def prepare(self, i: int) -> dict:
        self.cp.state.placement.release_stage(
            f"{generators_k8s.FLOW}/{MEASURED}")
        model = reference_k8s.measured_batch(self.model, i)
        return {"model": model,
                "request": generators_k8s.solve_request(model, MEASURED)}

    async def request(self, prepared: dict):
        return await self._solve_commit(prepared["request"])

    def verify(self, prepared: dict, result) -> tuple[int, list[str]]:
        faults = self._faults(MEASURED, result)
        init = self._committed(INIT)
        found = reference_k8s.check(
            prepared["model"],
            {INIT: dict(init.assignment) if init is not None else {},
             MEASURED: result[0]["assignment"]})
        if found["total"]:
            faults.append(f"reference check: {found}")
        return len(prepared["model"]["namespaces"][MEASURED]), faults

    async def close(self) -> None:
        await self.cp.stop()
