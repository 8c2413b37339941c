"""Op kind `submit_wait`: a wave of pending pods handed to the control
plane's admission queue in one request and waited for, beside pods that
are already running.

Set-up: CP in-process (`layers.ServedCp.start`), the deployment's nodes
registered online in its store; the stage attached with its init pods over
the wire by a first `deploy.submit` (`flow` + `stage`: the baseline solve
and commit), the record read back and checked; the reference schedules the
same cluster once, to show the instance has an answer that leaves no pod
pending. Op, timed from the request sent to the reply in hand: ONE
`deploy.submit` of the wave's arrivals — fresh names every op, as the
source's are new objects — with `wait`, so the reply is held until every
pod has its verdict: the server's drain loop pops micro-batches of
`admission_batch` events, each one resident delta solve and one
reservation committed, and the caller is told per pod `placed` and the
server. No `deploy.admit_status` is sent. Between ops, in `prepare` and
outside the timed part, the previous wave leaves through admission too
(`AdmissionController.submit` of its `departures`, drained by `step()`
in-process: `prepare` is not a coroutine), so the stage returns to its
init state with the wave's rows tombstoned for the next wave to reuse.

`verify` holds what the caller was told, together with the stage's record
read back from the store before and after the op, to
`reference_k8s_basic.check`: every pod placed on a known server within
capacity, told = committed, no pod that ran before on another server, no
departed pod in view. The op fails too if a verdict is anything but
`placed`, or if over the op the program parked or shed a request, moved a
running row (`fleet_admission_moved_rows_total`), served a micro-solve by
the greedy host fallback (`fleet_placement_churn_fallbacks_total`) or
compacted the stream (a cold re-stage).
"""

from __future__ import annotations

from benchmarks import generators_k8s_basic as generators
from benchmarks import layers
from benchmarks import reference_k8s_basic as reference
from benchmarks.spans import Watch, counter_sum

# what must not move over an op
UNMOVED = ("fleet_admission_moved_rows_total",
           "fleet_placement_churn_fallbacks_total",
           "fleet_admission_parked_total", "fleet_admission_sheds_total")


class Op:
    def __init__(self, cell):
        self.cell = cell
        self.wait_s = float(cell.traffic["params"]["wait_s"])
        self.last: dict | None = None       # the wave that is running

    def _committed(self) -> dict:
        rec = self.cp.state.store.find_one(
            "placements", lambda p: p.stage_key == generators.KEY)
        return dict(rec.assignment) if rec is not None else {}

    def _watched(self) -> dict[str, float]:
        values = Watch.counters()
        out = {name: counter_sum(values, name) for name in UNMOVED}
        out["compactions"] = float(
            self.cp.state.admission.stats["compactions"])
        return out

    async def setup(self) -> None:
        from fleetflow_tpu.cp.admission import AdmissionController
        from fleetflow_tpu.cp.models import ServerCapacity
        from fleetflow_tpu.cp.protocol import encode_frame

        if not hasattr(AdmissionController, "verdicts"):
            # a program from before a caller was told its verdicts: the
            # op cannot be run against it (it would have to poll)
            raise RuntimeError(
                "this program's deploy.submit has no `wait`: the cell "
                f"{self.cell.name} cannot run on it")
        cell = self.cell
        with cell.phase("generate"):
            self.model = generators.model(cell.config, cell.seed,
                                          cell.rehearsal)
            cell.notes["submit_request_bytes"] = len(encode_frame(
                {"type": "request", "id": 0, "channel": "deploy",
                 "method": "submit", "payload": generators.submit_request(
                     self.model["wave"], self.wait_s)}))
        with cell.phase("reference"):
            mine = reference.schedule(self.model, {})
            pending = sum(v is None for v in mine.values())
            found = reference.check(self.model, {}, mine, mine)
            cell.notes["reference"] = {"placed": len(mine) - pending,
                                       "pending": pending,
                                       "check": found["total"]}
            if pending or found["total"]:
                raise RuntimeError(f"the reference cannot place the "
                                   f"cluster: {pending} pending, {found}")
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
            reply = await self.cp.conn.request(
                "deploy", "submit", generators.attach_request(self.model),
                timeout=600)
            init = dict(self.model, wave=[])
            found = reference.check(init, {}, self._committed(), {})
            if reply["stage"] != generators.KEY or found["total"]:
                raise RuntimeError(f"init pods not placed: {reply} {found}")

    def prepare(self, i: int) -> dict:
        adm = self.cp.state.admission
        if self.last is not None:
            adm.submit(generators.TENANT, stage=generators.KEY,
                       departures=[p["name"] for p in self.last["wave"]])
            while adm.has_work():
                adm.step()
            self.last = None
        model = reference.wave(self.model, i)
        return {"model": model, "before": self._committed(),
                "watched": self._watched(),
                "request": generators.submit_request(model["wave"],
                                                     self.wait_s)}

    async def request(self, prepared: dict):
        # the wave is running from here on, whatever the reply says
        self.last = prepared["model"]
        return await self.cp.conn.request(
            "deploy", "submit", prepared["request"],
            timeout=self.wait_s + 60)

    def verify(self, prepared: dict, reply: dict) -> tuple[int, list[str]]:
        model = prepared["model"]
        faults = []
        verdicts = reply.get("verdicts")
        if verdicts is None:
            return 0, ["the reply carries no verdicts"]
        states: dict[str, int] = {}
        for v in verdicts:
            states[v["state"]] = states.get(v["state"], 0) + 1
        if states != {"placed": len(model["wave"])} or reply["pending"]:
            faults.append(f"verdicts {states}, pending {reply['pending']}: "
                          f"not every pod placed")
        for name, after in self._watched().items():
            moved = after - prepared["watched"][name]
            if moved:
                faults.append(f"{name} moved by {moved:g} during the op")
        told = {v["name"]: v.get("server") for v in verdicts}
        found = reference.check(model, prepared["before"],
                                self._committed(), told)
        if found["total"]:
            faults.append(f"reference check: {found}")
        return len(model["wave"]), faults

    async def close(self) -> None:
        await self.cp.stop()
