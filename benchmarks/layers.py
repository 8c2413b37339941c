"""Where the benchmark's spans attach to the program's layers.

Nothing in the program is edited: attributes are wrapped at run time
(`Spans.wrap`). Span names, from the outside in:

    op                  one client request, send to reply (and commit)
    cp_handler          a CP channel handler (cp/handlers.py), whole call
    placement.<method>  a PlacementService method (cp/placement.py)
    fanout              AgentRegistry.send_batch: commands out, acks back
    frontend            parse + aggregate + lower (registry/aggregate.py)
    sched               TpuSolverScheduler.place, which `reschedule` calls
                        too: staging, dispatch, the fetch of the result

The `sched` wrapper also takes samples from what the scheduler returned:
`solve_ms` (`Placement.solve_ms`: host clock, ends in a fetch — never a
device time) and `soft` (`Placement.soft`).
"""

from __future__ import annotations

from .spans import Spans

CP_CHANNELS = ("placement", "deploy")
PLACEMENT_METHODS = ("solve_stage", "node_events", "commit",
                     "commit_retained")


def wrap_scheduler(spans: Spans) -> None:
    from fleetflow_tpu.sched.tpu import TpuSolverScheduler

    def after(placement) -> None:
        spans.samples["solve_ms"].append(float(placement.solve_ms))
        spans.samples["soft"].append(float(placement.soft))

    spans.wrap(TpuSolverScheduler, "place", "sched", after=after)


class ServedCp:
    """The CP as it is served — `cp.server.start` with the TPU solver on,
    every other setting the server's default — with the benchmark's spans
    on it and one client connection to it."""

    @classmethod
    async def start(cls, spans: Spans) -> "ServedCp":
        from fleetflow_tpu.cp.protocol import ProtocolClient
        from fleetflow_tpu.cp.server import ServerConfig, start

        self = cls()
        self.handle = await start(ServerConfig(use_tpu_solver=True))
        self.state = self.handle.state
        wrap_cp(spans, self.handle)
        self.conn, self._task = await ProtocolClient.connect(
            self.handle.host, self.handle.port, identity="bench-client")
        return self

    async def stop(self) -> None:
        await self.conn.close()
        self._task.cancel()
        await self.handle.stop()


def wrap_cp(spans: Spans, handle) -> None:
    """`handle` is what `cp.server.start` returned; called before the
    first client connects."""
    for channel in CP_CHANNELS:
        spans.wrap(handle.server.handlers, channel, "cp_handler")
    for method in PLACEMENT_METHODS:
        spans.wrap(handle.state.placement, method, f"placement.{method}")
    spans.wrap(handle.state.agent_registry, "send_batch", "fanout")
