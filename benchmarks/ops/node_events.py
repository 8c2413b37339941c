"""Op kind `node_events`: one coalesced churn burst through the CP's
`placement` channel, then the retained re-solve committed.

Set-up: CP in-process (`cp.server.start`, TPU solver on), the deployment's
servers registered online in its store, the stage solved and committed.
Op: `placement.node_events` over a `ProtocolClient` connection — kill the
busiest live server and, once `max_dead` are down, revive the one killed
longest ago — reply received, then `PlacementService.commit_retained` on
the CP's state, as cp/reconverge.py does after a redeploy (the channel has
no method for it, so the call is in-process, inside the timed op).

traffic params: `max_dead`.
"""

from __future__ import annotations

import asyncio
from collections import Counter

from benchmarks import checker, generators, layers
from benchmarks.spans import Watch, counter_sum

FALLBACKS = "fleet_placement_churn_fallbacks_total"


class Op:
    def __init__(self, cell):
        self.cell = cell
        self.dead: list[str] = []

    async def setup(self) -> None:
        from fleetflow_tpu.core.serialize import flow_to_dict
        from fleetflow_tpu.cp.models import ServerCapacity
        from fleetflow_tpu.cp.protocol import RpcError, encode_frame

        cell = self.cell
        with cell.phase("generate"):
            flow, stage, model = generators.deployment(
                cell.config, cell.seed, cell.rehearsal)
            self.model = checker.Model(**model)
            self.key = f"{flow.name}/{stage}"
        with cell.phase("cp_start"):
            self.cp = await layers.ServedCp.start(cell.spans)
        state = self.cp.state
        with cell.phase("register_servers"):
            for slug, cap in model["servers"].items():
                rec = state.store.register_server(slug, tenant="default",
                                                  hostname=slug)
                state.store.update("servers", rec.id, status="online",
                                   capacity=ServerCapacity(**cap))
        with cell.phase("baseline_solve"):
            request = {"flow": flow_to_dict(flow), "stage": stage,
                       "reserve": True}
            try:
                encode_frame({"type": "request", "id": 0,
                              "channel": "placement", "method": "solve",
                              "payload": request})
                fits = True
            except RpcError:
                fits = False
            cell.notes["baseline_solve_over_the_wire"] = fits
            if fits:
                reply = await self.cp.conn.request("placement", "solve",
                                                request, timeout=600)
                feasible, rid = reply["feasible"], reply["reservation"]
                self.assignment = reply["assignment"]
            else:
                # the stage does not fit one protocol frame; set-up is not
                # the path being timed
                placement, rid = await asyncio.get_running_loop(
                    ).run_in_executor(None, lambda: state.placement
                                      .solve_stage(flow, stage))
                feasible = placement.feasible
                self.assignment = placement.assignment
            if not feasible or not rid or not state.placement.commit(rid):
                raise RuntimeError("baseline solve infeasible or "
                                   "its commit refused")
            faults = checker.check(self.model, self.assignment)
            if faults["total"]:
                raise RuntimeError(f"baseline placement wrong: {faults}")
        self.fallbacks = counter_sum(Watch.counters(), FALLBACKS)

    def prepare(self, i: int) -> list[dict]:
        down = set(self.dead)
        loads = Counter(n for n in self.assignment.values()
                        if n not in down)
        victim = max(sorted(loads), key=loads.__getitem__)
        events = [{"slug": victim, "online": False}]
        if len(self.dead) >= self.cell.traffic["params"]["max_dead"]:
            events.append({"slug": self.dead.pop(0), "online": True})
        self.dead.append(victim)
        return events

    async def request(self, events: list[dict]):
        reply = await self.cp.conn.request("placement", "node_events",
                                           {"events": events}, timeout=120)
        committed = self.cp.state.placement.commit_retained(self.key)
        return reply, committed

    def verify(self, events, result) -> tuple[int, list[str]]:
        """(rows placed, faults); no fault means the op counts."""
        reply, committed = result
        faults = []
        moved = reply["rescheduled"]
        if [m["stage"] for m in moved] != [self.key]:
            return 0, [f"stage not re-solved: {[m['stage'] for m in moved]}"]
        if not moved[0]["feasible"]:
            faults.append("re-solve infeasible")
        if not committed:
            faults.append("commit refused")
        fallbacks = counter_sum(Watch.counters(), FALLBACKS)
        if fallbacks != self.fallbacks:
            self.fallbacks = fallbacks
            faults.append("served by the host greedy fallback")
        self.assignment = moved[0]["assignment"]
        found = checker.check(self.model, self.assignment,
                              offline=self.dead)
        if found["total"]:
            faults.append(f"checker: {found}")
        return len(self.model.rows), faults

    async def close(self) -> None:
        await self.cp.stop()
