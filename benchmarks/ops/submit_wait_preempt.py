"""Op kind `submit_wait_preempt`: a wave of pending high-priority pods
handed to the control plane's admission queue in one request and waited
for, on a cluster that low-priority pods of another namespace have filled,
so that every micro-batch of the wave evicts.

Set-up: first, the program's admission is asked whether it takes a
`priority` on a streamed arrival at all — a wire spec with one, built into
a streamed service (`AdmissionController.make_arrival`) that keeps it and
that `_simple_reject` lets stream — and a program that does not is refused
at once, before anything is generated or started: it would either refuse
the wave or stream pods that fit nowhere. Then CP in-process
(`layers.ServedCp.start`), the deployment's nodes registered online;
namespace sched-0 (the init pods, four a node, no room for a fifth) solved
and committed through `PlacementService.solve_stage` + `commit`
in-process, as `solve_commit_preempt` does, and checked; namespace sched-1
attached EMPTY by a first `deploy.submit` (`flow` + `stage`); the
reference schedules the same cluster once (init pods, then one wave,
preempting), to show the instance has an answer and how many victims it
forces. Op, timed from the request sent to the reply in hand: ONE
`deploy.submit` of the wave's arrivals to sched-1 — fresh names every op
— with `wait`: the server's drain loop pops micro-batches of
`admission_batch` events, each folded with its priority, solved on the
resident delta path with what sched-0's rows hold counted and priced, its
victims selected and claimed by one reservation, whose commit evicts them;
the caller is told per pod `placed` and the server. Between ops, in
`prepare` and outside the timed part, the previous wave leaves through
admission (`departures`, drained by `step()` in-process) and
`PlacementService.reinstate("k8s/sched-0")` puts its victims back where
they were: each op starts from the init state.

`verify` holds what the caller was told, with both namespaces' records and
every touched server record read back from the store before and after the
op, to `reference_k8s_preempt_admit.check`: every pod placed and told,
capacity over the survivors and the arrivals, no victim of no lower
priority or needless, the reference's number of victims, each touched
server's `allocated` the sum of what remains, no pod moved, none gone in
view. The op fails too if a verdict is anything but `placed`, if it did
not start from the init state, or if over the op the program parked or
shed a request, moved a running row, served a micro-solve by the greedy
host fallback or a relaxed rung, or compacted the stream. How many
`sched.place` calls a micro-batch made over the ops is a note
(`places_per_pass`): one, where a micro-batch solves once.
"""

from __future__ import annotations

from benchmarks import generators_k8s_preempt_admit as generators
from benchmarks import layers
from benchmarks import reference_k8s_preempt_admit as reference
from benchmarks.reference_k8s_preemption import INIT, MEASURED
from benchmarks.spans import Watch, counter_sum

# what must not move over an op
UNMOVED = ("fleet_admission_moved_rows_total",
           "fleet_placement_churn_fallbacks_total",
           "fleet_admission_parked_total", "fleet_admission_sheds_total",
           "fleet_sched_relaxed_total")
PASSES = "fleet_admission_solves_total"


def streams_priority() -> bool:
    """Whether this program's streaming admission takes a `priority` on an
    arrival and keeps it (a program from before refuses the key, or drops
    it and streams the pod at priority 0)."""
    from fleetflow_tpu.cp import admission

    model = reference.cluster(0, 1, 0, 1)
    probe = generators.arrivals(model["namespaces"][MEASURED])[0]
    try:
        svc = admission.AdmissionController(None).make_arrival(probe)
    except (TypeError, ValueError):
        return False
    return (getattr(svc, "priority", 0) == probe["priority"]
            and admission._simple_reject(svc) is None)


def stage_key(namespace: str) -> str:
    return f"{generators.FLOW}/{namespace}"


class Op:
    def __init__(self, cell):
        self.cell = cell
        self.wait_s = float(cell.traffic["params"]["wait_s"])
        self.last: dict | None = None       # the wave that is running
        self.passes = self.places = 0

    def _committed(self) -> dict:
        """Both namespaces' placement records, read back from the store."""
        out = {}
        for ns in (INIT, MEASURED):
            key = stage_key(ns)
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

    def _counts(self) -> tuple[float, int]:
        """Micro-batches committed so far, and `sched.place` calls."""
        return (counter_sum(Watch.counters(), PASSES),
                self.cell.spans.total("sched")[1])

    async def setup(self) -> None:
        from fleetflow_tpu.cp.models import ServerCapacity
        from fleetflow_tpu.cp.protocol import encode_frame

        if not streams_priority():
            raise RuntimeError(
                "this program's streaming admission does not take a "
                "`priority` on a streamed arrival (deploy.submit refuses "
                f"the key or drops it): the cell {self.cell.name} cannot "
                f"run on it")
        cell = self.cell
        with cell.phase("generate"):
            self.model = generators.model(cell.config, cell.seed,
                                          cell.rehearsal)
            cell.notes["submit_request_bytes"] = len(encode_frame(
                {"type": "request", "id": 0, "channel": "deploy",
                 "method": "submit", "payload": generators.submit_request(
                     self.model["namespaces"][MEASURED], self.wait_s)}))
        with cell.phase("reference"):
            mine, victims = reference.schedule(self.model)
            self.forced = sum(map(len, victims.values()))
            stay = {n: s for n, s in mine[INIT].items() if s is not None}
            found = reference.check(
                self.model, {INIT: {**stay, **victims.get(INIT, {})}},
                {INIT: stay, MEASURED: mine[MEASURED]}, mine[MEASURED],
                forced=self.forced)
            cell.notes["reference"] = {
                "placed": {ns: sum(v is not None for v in a.values())
                           for ns, a in mine.items()},
                "victims": self.forced, "check": found["total"]}
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
            if not placement.feasible or not state.placement.commit(rid):
                raise RuntimeError(f"init pods not placed: "
                                   f"{placement.violations} violations")
            self.init = self._committed()[INIT]
            found = reference.check(
                dict(self.model, namespaces={
                    INIT: self.model["namespaces"][INIT], MEASURED: []}),
                {}, {INIT: self.init}, {})
            if found["total"]:
                raise RuntimeError(f"init pods not placed: {found}")
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
            self.cp.state.placement.reinstate(stage_key(INIT))
            self.last = None
        model = reference.wave(self.model, i)
        return {"model": model, "before": self._committed(),
                "watched": self._watched(), "counts": self._counts(),
                "request": generators.submit_request(
                    model["namespaces"][MEASURED], self.wait_s)}

    async def request(self, prepared: dict):
        # the wave is running from here on, whatever the reply says
        self.last = prepared["model"]
        return await self.cp.conn.request(
            "deploy", "submit", prepared["request"],
            timeout=self.wait_s + 60)

    def _allocated(self, after: dict, before: dict) -> dict:
        """(cpu, memory) allocated on each node the op touched, as the
        store holds it."""
        gone = set(before[INIT]) - set(after[INIT])
        touched = set(after[MEASURED].values()) | {
            before[INIT][name] for name in gone}
        store = self.cp.state.store
        out = {}
        for slug in touched:
            s = store.server_by_slug(slug)
            if s is not None:
                out[slug] = (s.allocated.cpu, s.allocated.memory)
        return out

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
        before = prepared["before"]
        if before[INIT] != self.init:
            faults.append(f"started from {len(before[INIT])} init pods "
                          f"where they were, not {len(self.init)}")
        for name, after in self._watched().items():
            moved = after - prepared["watched"][name]
            if moved:
                faults.append(f"{name} moved by {moved:g} during the op")
        passes, places = self._counts()
        self.passes += passes - prepared["counts"][0]
        self.places += places - prepared["counts"][1]
        if self.passes:
            self.cell.notes["places_per_pass"] = self.places / self.passes
        told = {v["name"]: v.get("server") for v in verdicts}
        after = self._committed()
        found = reference.check(
            model, before, after, told, self._allocated(after, before),
            self.forced)
        if found["total"]:
            faults.append(f"reference check: {found}")
        return len(wave), faults

    async def close(self) -> None:
        await self.cp.stop()
