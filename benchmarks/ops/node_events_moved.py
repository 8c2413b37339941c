"""Op kind `node_events_moved`: `node_events`' churn burst, asked for the
reply that carries what moved, at a size whose whole assignment is no one
frame.

Set-up and traffic are `ops/node_events.py`'s (kill the busiest live
server; once `max_dead` are down revive the one dead longest; the retained
re-solve committed in-process, inside the timed op). The request carries
`"reply": "moved"` and the reply, per re-solved stage, `{"stage",
"feasible", "rows", "moved": {row: server}}`: the client applies the map
to the assignment it holds.

Checked on every reply, outside the timed part: the map against
`benchmarks/reference_churn.py` (every displaced row in it, none mapped to
where it was or onto a dead server, no unknown row, the stage's row
count); the applied assignment, all of its rows, by `checker.check`; the
committed record read back from the store equal to it; and that the
mesh-sharded annealer served the op from its resident state — one more
`fleet_solver_sharded_solves_total{outcome="delta"}`, no host fallback.
The last is skipped only under `--cpu-rehearsal`, whose one CPU device
cannot be a mesh, and the run's notes say so.

traffic params: `max_dead`.
"""

from __future__ import annotations

# first, before any set-up: a program that cannot answer in the form this
# op asks for fails here, in seconds
from fleetflow_tpu.cp.handlers import NODE_EVENTS_REPLY_FORMS

from benchmarks import checker, reference_churn
from benchmarks.ops import node_events
from benchmarks.spans import Watch, counter_sum

FORM = "moved"
if FORM not in NODE_EVENTS_REPLY_FORMS:
    raise ImportError(f"the program's placement.node_events has no reply "
                      f"form {FORM!r}: {NODE_EVENTS_REPLY_FORMS}")

MESH_DELTA = 'fleet_solver_sharded_solves_total{outcome="delta"}'


class Op(node_events.Op):
    async def setup(self) -> None:
        await super().setup()
        self.cell.notes["mesh_check"] = (
            "skipped: one CPU device cannot route to the mesh"
            if self.cell.rehearsal else "on")
        self.mesh_deltas = counter_sum(Watch.counters(), MESH_DELTA)

    async def request(self, events: list[dict]):
        reply = await self.cp.conn.request(
            "placement", "node_events", {"events": events, "reply": FORM},
            timeout=120)
        committed = self.cp.state.placement.commit_retained(self.key)
        return reply, committed

    def verify(self, events, result) -> tuple[int, list[str]]:
        """(rows placed, faults); no fault means the op counts."""
        reply, committed = result
        entries = reply["rescheduled"]
        if [e["stage"] for e in entries] != [self.key]:
            return 0, [f"stage not re-solved: {[e['stage'] for e in entries]}"]
        entry = entries[0]
        faults = []
        if not entry["feasible"]:
            faults.append("re-solve infeasible")
        if not committed:
            faults.append("commit refused")
        counters = Watch.counters()
        fallbacks = counter_sum(counters, node_events.FALLBACKS)
        if fallbacks != self.fallbacks:
            self.fallbacks = fallbacks
            faults.append("served by the host greedy fallback")
        deltas = counter_sum(counters, MESH_DELTA)
        if not self.cell.rehearsal and deltas != self.mesh_deltas + 1:
            faults.append(f"not served from the mesh's resident state: "
                          f"{deltas - self.mesh_deltas:g} sharded delta "
                          f"solves this op")
        self.mesh_deltas = deltas

        # of what moved, how much the burst forced: the rows that sat on a
        # server now dead (the notes carry the totals over every op
        # checked, the warm-up's too)
        notes = self.cell.notes
        notes["ops_checked"] = notes.get("ops_checked", 0) + 1
        notes["moved_rows"] = notes.get("moved_rows", 0) + len(entry["moved"])
        notes["displaced_rows"] = notes.get("displaced_rows", 0) + sum(
            1 for row in entry["moved"]
            if self.assignment.get(row) in self.dead)
        found = reference_churn.check_moved(
            self.assignment, entry["moved"], self.dead, entry["rows"])
        if found["total"]:
            faults.append(f"moved: {found}")
        self.assignment = reference_churn.apply_moved(self.assignment,
                                                      entry["moved"])
        found = checker.check(self.model, self.assignment, offline=self.dead)
        if found["total"]:
            faults.append(f"checker: {found}")
        record = self.cp.state.store.find_one(
            "placements", lambda p: p.stage_key == self.key)
        if record is None or record.assignment != self.assignment:
            faults.append("the committed record read back is not the "
                          "client's assignment")
        return len(self.model.rows), faults
