"""Op kind `submit_wait_anti`: a wave of pending anti-affine pods handed to
the control plane's admission queue in one request and waited for, beside
another namespace's anti-affine pods that are already running.

Set-up: first, the program's admission is asked whether it takes an
anti-affine arrival at all — a wire spec with the term, built into a
streamed service (`AdmissionController.make_arrival`) that keeps it and
that `_simple_reject` lets stream — and a program that does not is refused
at once, before anything is generated or started: it would either refuse
the wave or drop the term and place pods that the check then faults. Then
CP in-process (`layers.ServedCp.start`), the deployment's nodes registered
online; namespace sched-0 (the init pods) solved and committed over the
wire by `placement.solve` + `placement.commit`, as `solve_commit` does,
and checked; namespace sched-1 attached EMPTY by a first `deploy.submit`
(`flow` + `stage`); the reference schedules the same cluster once, to show
the instance has an answer. Op, timed from the request sent to the reply
in hand: ONE `deploy.submit` of the wave's arrivals to sched-1 — fresh
names every op — with `wait`: the server's drain loop pops micro-batches
of `admission_batch` events, each folded with its keys, barred from the
servers sched-0 holds its key on, solved on the resident delta path and
committed as one reservation, and the caller is told per pod `placed` and
the server. Between ops, in `prepare` and outside the timed part, the
previous wave leaves through admission (`departures`, drained by `step()`
in-process), so sched-1 is back at no live row with the wave's rows
tombstoned for the next wave to reuse.

`verify` holds what the caller was told, with both namespaces' records
read back from the store before and after the op, to
`reference_k8s_anti_admit.check`: every pod of both namespaces placed on a
known server within capacity, no two green pods a server over both
namespaces, told = committed, no pod that ran before on another server,
no departed pod in view. The op fails too if a verdict is anything but
`placed`, or if over the op the program parked or shed a request, moved a
running row (`fleet_admission_moved_rows_total`), served a micro-solve by
the greedy host fallback (`fleet_placement_churn_fallbacks_total`) or a
relaxed rung (`fleet_sched_relaxed_total`), or compacted the stream.
"""

from __future__ import annotations

from benchmarks import generators_k8s_anti_admit as generators
from benchmarks import layers
from benchmarks import reference_k8s_anti_admit as reference
from benchmarks.reference_k8s import INIT, MEASURED
from benchmarks.spans import Watch, counter_sum

# what must not move over an op
UNMOVED = ("fleet_admission_moved_rows_total",
           "fleet_placement_churn_fallbacks_total",
           "fleet_admission_parked_total", "fleet_admission_sheds_total",
           "fleet_sched_relaxed_total")


def streams_anti_affinity() -> bool:
    """Whether this program's streaming admission takes an anti-affine
    arrival and keeps its term (a program from before refuses the spec,
    or drops the term and streams the pod as if it had none)."""
    from fleetflow_tpu.cp import admission

    probe = generators.arrivals([reference.cluster(0, 1, 0, 1)[
        "namespaces"][MEASURED][0]])[0]
    try:
        svc = admission.AdmissionController(None).make_arrival(probe)
    except (TypeError, ValueError):
        return False
    return (bool(svc.anti_affinity) and bool(svc.anti_affinity_stages)
            and admission._simple_reject(svc) is None)


class Op:
    def __init__(self, cell):
        self.cell = cell
        self.wait_s = float(cell.traffic["params"]["wait_s"])
        self.last: dict | None = None       # the wave that is running

    def _committed(self) -> dict:
        """Both namespaces' placement records, read back from the store."""
        out = {}
        for ns in (INIT, MEASURED):
            key = f"{generators.FLOW}/{ns}"
            rec = self.cp.state.store.find_one(
                "placements", lambda p, key=key: p.stage_key == key)
            out[ns] = dict(rec.assignment) if rec is not None else {}
        return out

    def _watched(self) -> dict[str, float]:
        values = Watch.counters()
        out = {name: counter_sum(values, name) for name in UNMOVED}
        out["compactions"] = float(
            self.cp.state.admission.stats["compactions"])
        return out

    async def setup(self) -> None:
        from fleetflow_tpu.cp.models import ServerCapacity
        from fleetflow_tpu.cp.protocol import encode_frame

        if not streams_anti_affinity():
            raise RuntimeError(
                "this program's streaming admission does not take an "
                "anti-affine arrival (deploy.submit refuses the term or "
                f"drops it): the cell {self.cell.name} cannot run on it")
        cell = self.cell
        with cell.phase("generate"):
            self.model = generators.model(cell.config, cell.seed,
                                          cell.rehearsal)
            cell.notes["submit_request_bytes"] = len(encode_frame(
                {"type": "request", "id": 0, "channel": "deploy",
                 "method": "submit", "payload": generators.submit_request(
                     self.model["namespaces"][MEASURED], self.wait_s)}))
        with cell.phase("reference"):
            mine = reference.schedule(self.model)
            found = reference.check(self.model, {}, mine, mine[MEASURED])
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
                        **generators.server_capacity(node)))
        with cell.phase("baseline_solve"):
            reply = await self.cp.conn.request(
                "placement", "solve", generators.init_request(self.model),
                timeout=600)
            done = await self.cp.conn.request(
                "placement", "commit", {"reservation": reply["reservation"]},
                timeout=600)
            init = self._committed()
            found = reference.check(
                dict(self.model, namespaces={
                    INIT: self.model["namespaces"][INIT], MEASURED: []}),
                {}, init, {})
            if not done["ok"] or found["total"]:
                raise RuntimeError(f"init pods not placed: {done} {found}")
            opened = await self.cp.conn.request(
                "deploy", "submit", generators.attach_request(),
                timeout=600)
            if opened["stage"] != generators.KEY:
                raise RuntimeError(f"sched-1 not attached: {opened}")

    def prepare(self, i: int) -> dict:
        adm = self.cp.state.admission
        if self.last is not None:
            adm.submit(generators.TENANT, stage=generators.KEY,
                       departures=[p["name"] for p in
                                   self.last["namespaces"][MEASURED]])
            while adm.has_work():
                adm.step()
            self.last = None
        model = reference.wave(self.model, i)
        return {"model": model, "before": self._committed(),
                "watched": self._watched(),
                "request": generators.submit_request(
                    model["namespaces"][MEASURED], self.wait_s)}

    async def request(self, prepared: dict):
        # the wave is running from here on, whatever the reply says
        self.last = prepared["model"]
        return await self.cp.conn.request(
            "deploy", "submit", prepared["request"],
            timeout=self.wait_s + 60)

    def verify(self, prepared: dict, reply: dict) -> tuple[int, list[str]]:
        model = prepared["model"]
        wave = model["namespaces"][MEASURED]
        faults = []
        verdicts = reply.get("verdicts")
        if verdicts is None:
            return 0, ["the reply carries no verdicts"]
        states: dict[str, int] = {}
        for v in verdicts:
            states[v["state"]] = states.get(v["state"], 0) + 1
        if states != {"placed": len(wave)} or reply["pending"]:
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
        return len(wave), faults

    async def close(self) -> None:
        await self.cp.stop()
