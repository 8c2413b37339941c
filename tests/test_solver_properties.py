import numpy as np
import pytest

from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.core.model import PlacementStrategy
from fleetflow_tpu.solver import prepare_problem, solve
from fleetflow_tpu.solver.repair import verify


class TestSolverPropertySweep:
    """Randomized-instance sweep (r5): the bench pins three canonical
    instances; this pins the CLAIM — for any generatable instance the
    solver either returns an exactly feasible assignment or says
    infeasible, the device result agrees with the independent host
    verifier, and warm re-solves preserve the contract under churn."""

    @pytest.mark.parametrize("seed", range(8))
    def test_random_instances_solve_clean(self, seed):
        rng = np.random.default_rng(1000 + seed)
        S = int(rng.integers(50, 400))
        N = int(rng.integers(5, 40))
        strategy = [PlacementStrategy.SPREAD_ACROSS_POOL,
                    PlacementStrategy.PACK_INTO_DEDICATED,
                    PlacementStrategy.FILL_LOWEST][seed % 3]
        pt = synthetic_problem(
            S, N, seed=2000 + seed,
            dep_depth_max=int(rng.integers(1, 6)),
            port_fraction=float(rng.uniform(0.0, 0.4)),
            volume_fraction=float(rng.uniform(0.0, 0.2)),
            n_tenants=int(rng.integers(1, 5)),
            strategy=strategy)
        res = solve(pt, steps=128, seed=seed)
        host = verify(pt, res.assignment)
        # device verdict must agree with the independent host verifier
        assert int(host["total"]) == res.violations
        if res.feasible:
            assert res.violations == 0
        # assignment is always in range and complete
        assert res.assignment.shape == (pt.S,)
        assert (res.assignment >= 0).all() and (res.assignment < pt.N).all()

    @pytest.mark.parametrize("seed", range(4))
    def test_warm_resolve_after_churn_stays_clean(self, seed):
        import dataclasses
        pt = synthetic_problem(150, 12, seed=3000 + seed, n_tenants=2,
                               port_fraction=0.25, volume_fraction=0.1)
        res = solve(pt, steps=128, seed=seed)
        assert res.feasible
        rng = np.random.default_rng(seed)
        # kill 2 random nodes that host something
        used_nodes = np.unique(res.assignment)
        dead = rng.choice(used_nodes, size=min(2, len(used_nodes) - 1),
                          replace=False)
        valid = pt.node_valid.copy()
        valid[dead] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        res2 = solve(pt2, steps=128, seed=seed + 1,
                     init_assignment=res.assignment)
        host = verify(pt2, res2.assignment)
        assert int(host["total"]) == res2.violations
        if not res2.feasible:
            # the solver may only declare defeat when the instance is
            # PROVABLY infeasible: some conflict group has more members
            # than surviving nodes (each member needs a distinct node).
            # Seed 0 hits exactly this — an 11-member port group against
            # 10 valid nodes — and both warm and cold solves correctly
            # report one irreducible conflict.
            witness = False
            n_valid = int(valid.sum())
            for ids in (pt2.port_ids, pt2.volume_ids, pt2.anti_ids):
                if ids.size == 0:
                    continue
                flat = ids[ids >= 0]
                if flat.size and int(np.bincount(flat).max()) > n_valid:
                    witness = True
            assert witness, (
                f"solver reported infeasible without a pigeonhole witness: "
                f"{res2.stats}")
            return
        assert not np.isin(res2.assignment, dead).any()
        # migration stickiness: services NOT on dead nodes mostly stay
        unaffected = ~np.isin(res.assignment, dead)
        moved_unaffected = (res2.assignment != res.assignment) & unaffected
        assert moved_unaffected.mean() < 0.5


class TestShardedPropertySweep:
    @pytest.mark.parametrize("seed", range(4))
    def test_random_instances_shard_to_feasibility(self, seed):
        """The service-axis SPMD path must reach the same contract as the
        single-device solver on random instances: exact feasibility by the
        independent host verifier, from a deliberately bad start (every
        service on node 0) so the sweep does real work.

        The single-device contract (solver/api.solve) is anneal + the
        host repair backstop -> "zero violations or infeasible"; the
        kernel alone may plateau a handful of sweeps short on a hard
        instance (seed 3 on the 8-device mesh parks one port conflict at
        400 steps and clears it by ~640). So this pins BOTH halves:
        the kernel must get within a small repairable distance (<= 3
        violations — the backstop is a backstop, not the solver), and
        repair must land exact feasibility, same as the production path."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from fleetflow_tpu.solver import prepare_problem
        from fleetflow_tpu.solver.repair import repair
        from fleetflow_tpu.solver.sharded import (SVC_AXIS, anneal_sharded,
                                                  pad_problem)

        rng = np.random.default_rng(7000 + seed)
        N = int(rng.integers(6, 24))
        S = int(rng.integers(8, 40)) * 8 - int(rng.integers(0, 7))  # ragged
        pt = synthetic_problem(S, N, seed=8000 + seed,
                               port_fraction=float(rng.uniform(0, 0.25)),
                               volume_fraction=float(rng.uniform(0, 0.1)),
                               n_tenants=int(rng.integers(1, 4)))
        padded, orig_s = pad_problem(prepare_problem(pt), 8)
        mesh = Mesh(np.array(jax.devices()[:8]), (SVC_AXIS,))
        out, sweeps = anneal_sharded(
            padded, jnp.zeros((padded.S,), jnp.int32),
            jax.random.PRNGKey(seed), steps=400, mesh=mesh, block=16,
            n_real=orig_s, return_sweeps=True)
        a = np.asarray(out)[:orig_s]
        assert (a >= 0).all() and (a < N).all()
        pre = verify(pt, a)
        assert pre["total"] <= 3, (S, N, pre, int(sweeps))
        fixed = repair(pt, a, seed=seed)
        post = verify(pt, fixed.assignment)
        assert post["total"] == 0, (S, N, pre, post, fixed.moves)
        assert (fixed.assignment >= 0).all() and (fixed.assignment < N).all()
