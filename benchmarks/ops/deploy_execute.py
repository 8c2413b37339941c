"""Op kind `deploy_execute`: an operator's `fleet deploy` of one stage
through the CP, to its agents and back.

Set-up: CP in-process (`cp.server.start`, TPU solver on) and one
`agent.Agent` per server of the deployment in the same process, each on a
`MockBackend` (no container runtime is under test), registered and
connected over real `ProtocolClient` connections. Op: `deploy.execute`
over a `ProtocolClient` connection -> `execute_deploy` -> solve ->
`send_batch` to the agents -> acks -> commit -> reply, as
`cli/main.py cmd_deploy` sends it (static services are not sent to the
engine). The first deploy is one of the warm-up ops.
"""

from __future__ import annotations

import asyncio

from benchmarks import checker, generators, layers


class Op:
    def __init__(self, cell):
        self.cell = cell

    async def setup(self) -> None:
        from fleetflow_tpu.agent.agent import Agent, AgentConfig
        from fleetflow_tpu.core.model import ServiceType
        from fleetflow_tpu.runtime.backend import MockBackend
        from fleetflow_tpu.runtime.engine import DeployRequest

        cell = self.cell
        with cell.phase("generate"):
            flow, stage, model = generators.deployment(
                cell.config, cell.seed, cell.rehearsal)
            self.model = checker.Model(**model)
            self.key = f"{flow.name}/{stage}"
            containers = [s.name for s in flow.stage(stage)
                          .resolved_services(flow)
                          if s.service_type is not ServiceType.STATIC]
            self.payload = {
                "request": DeployRequest(
                    flow=flow, stage_name=stage,
                    target_services=containers).to_dict(),
                "tenant": flow.tenant.name if flow.tenant else "default"}
        with cell.phase("cp_start"):
            self.cp = await layers.ServedCp.start(cell.spans)
        with cell.phase("agents"):
            self.agents = [
                Agent(AgentConfig(cp_host=self.cp.handle.host,
                                  cp_port=self.cp.handle.port, slug=slug,
                                  capacity=dict(cap)),
                      backend=MockBackend(auto_pull=True),
                      sleep=lambda _s: None)
                for slug, cap in model["servers"].items()]
            self.sessions = [asyncio.ensure_future(a.run_session())
                             for a in self.agents]
            registry = self.cp.state.agent_registry
            for _ in range(600):
                if all(registry.is_connected(a.config.slug)
                       for a in self.agents):
                    break
                await asyncio.sleep(0.05)
            else:
                raise RuntimeError("agents did not connect")

    def prepare(self, i: int) -> dict:
        return self.payload

    async def request(self, payload: dict) -> dict:
        return await self.cp.conn.request("deploy", "execute", payload,
                                          timeout=120)

    def verify(self, payload, reply) -> tuple[int, list[str]]:
        dep = reply["deployment"]
        faults = []
        if dep["status"] != "succeeded":
            faults.append(f"deployment {dep['status']}: {dep.get('error')}")
        assignment = dep.get("placement") or {}
        found = checker.check(self.model, assignment)
        if found["total"]:
            faults.append(f"checker: {found}")
        rec = self.cp.state.store.find_one(
            "placements", lambda p: p.stage_key == self.key)
        if rec is None or dict(rec.assignment) != assignment:
            faults.append("placement not committed")
        return len(self.model.rows), faults

    async def close(self) -> None:
        for agent in self.agents:
            agent.stop()
        await asyncio.wait(self.sessions, timeout=10)
        await self.cp.stop()
