"""Solver tests: kernels vs numpy ground truth, greedy, anneal, solve
pipeline on the BASELINE eval configs (CPU tier — the analog of the
reference's no-Docker fast tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetflow_tpu.core import parse_kdl_string
from fleetflow_tpu.core.model import PlacementStrategy
from fleetflow_tpu.lower import lower_stage, synthetic_problem
from fleetflow_tpu.solver import (greedy_place, placement_order,
                                  prepare_problem, repair, solve,
                                  verify, violation_stats)


def random_assignment(pt, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, pt.N, pt.S).astype(np.int32)


class TestKernelsMatchNumpy:
    """Device violation_stats must agree exactly with host verify()."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_assignments(self, seed):
        pt = synthetic_problem(60, 6, seed=seed)
        prob = prepare_problem(pt)
        a = random_assignment(pt, seed)
        dev = {k: float(v) for k, v in
               violation_stats(prob, jnp.asarray(a)).items()}
        host = verify(pt, a)
        for k in ("capacity", "conflicts", "eligibility", "skew", "total"):
            assert dev[k] == pytest.approx(host[k]), (k, dev, host)

    def test_multi_tenant_eligibility_counted(self):
        pt = synthetic_problem(80, 8, seed=3, n_tenants=3)
        prob = prepare_problem(pt)
        a = random_assignment(pt, 3)
        dev = violation_stats(prob, jnp.asarray(a))
        host = verify(pt, a)
        assert float(dev["eligibility"]) == host["eligibility"] > 0

    def test_zero_on_feasible_toy(self):
        # 2 services, 2 nodes, same host port → must split; assignment [0,1]
        flow = parse_kdl_string('''
server "n1" { capacity { cpu 1; memory "1g" } }
server "n2" { capacity { cpu 1; memory "1g" } }
service "a" { ports { port host=80 container=80 } resources { cpu 0.5; memory 256 } }
service "b" { ports { port host=80 container=80 } resources { cpu 0.5; memory 256 } }
stage "s" { service "a"; service "b" }
''')
        pt = lower_stage(flow, "s")
        prob = prepare_problem(pt)
        good = jnp.array([0, 1], dtype=jnp.int32)
        bad = jnp.array([0, 0], dtype=jnp.int32)
        assert float(violation_stats(prob, good)["total"]) == 0
        assert float(violation_stats(prob, bad)["conflicts"]) == 1


class TestGreedy:
    def test_three_tier_local(self):
        # BASELINE config 1: postgres→redis→app on the implicit local node
        flow = parse_kdl_string('''
service "postgres" { ports { port host=5432 container=5432 } }
service "redis" { }
service "app" { depends_on "postgres" "redis" }
stage "local" { service "postgres"; service "redis"; service "app" }
''')
        pt = lower_stage(flow, "local")
        prob = prepare_problem(pt)
        order = jnp.asarray(placement_order(pt.demand, pt.dep_depth, np.asarray(prob.conflict_ids)))
        a = greedy_place(prob, order)
        assert verify(pt, np.asarray(a))["total"] == 0
        assert set(np.asarray(a).tolist()) == {0}

    def test_synthetic_100x10_feasible(self):
        # BASELINE config 2
        pt = synthetic_problem(100, 10, seed=0)
        prob = prepare_problem(pt)
        order = jnp.asarray(placement_order(pt.demand, pt.dep_depth, np.asarray(prob.conflict_ids)))
        a = np.asarray(greedy_place(prob, order))
        stats = verify(pt, a)
        assert stats["total"] == 0, stats

    def test_port_anti_affinity_respected(self):
        pt = synthetic_problem(120, 12, seed=1, port_fraction=0.5)
        prob = prepare_problem(pt)
        order = jnp.asarray(placement_order(pt.demand, pt.dep_depth, np.asarray(prob.conflict_ids)))
        a = np.asarray(greedy_place(prob, order))
        assert verify(pt, a)["conflicts"] == 0

    def test_eligibility_respected(self):
        pt = synthetic_problem(90, 9, seed=2, n_tenants=3)
        prob = prepare_problem(pt)
        order = jnp.asarray(placement_order(pt.demand, pt.dep_depth, np.asarray(prob.conflict_ids)))
        a = np.asarray(greedy_place(prob, order))
        assert verify(pt, a)["eligibility"] == 0

    def test_pack_strategy_uses_fewer_nodes(self):
        pt_s = synthetic_problem(60, 10, seed=4,
                                 strategy=PlacementStrategy.SPREAD_ACROSS_POOL)
        pt_p = synthetic_problem(60, 10, seed=4,
                                 strategy=PlacementStrategy.PACK_INTO_DEDICATED)
        o = jnp.asarray(placement_order(pt_s.demand, pt_s.dep_depth))
        a_s = np.asarray(greedy_place(prepare_problem(pt_s), o))
        a_p = np.asarray(greedy_place(prepare_problem(pt_p), o))
        assert len(set(a_p.tolist())) <= len(set(a_s.tolist()))


class TestRepair:
    def test_repairs_random_assignment(self):
        pt = synthetic_problem(80, 10, seed=5)
        bad = random_assignment(pt, 5)
        assert verify(pt, bad)["total"] > 0
        rr = repair(pt, bad)
        assert rr.feasible, rr.stats
        assert rr.moves > 0

    def test_repair_noop_on_feasible(self):
        pt = synthetic_problem(50, 8, seed=6)
        prob = prepare_problem(pt)
        order = jnp.asarray(placement_order(pt.demand, pt.dep_depth, np.asarray(prob.conflict_ids)))
        a = np.asarray(greedy_place(prob, order))
        rr = repair(pt, a)
        assert rr.moves == 0
        assert np.array_equal(rr.assignment, a)


class TestSolve:
    def test_config2_zero_violations(self):
        pt = synthetic_problem(100, 10, seed=0)
        res = solve(pt, chains=4, steps=300, seed=0)
        assert res.feasible, res.stats
        assert res.assignment.shape == (100,)
        # the DEVICE solver must produce the feasible assignment itself —
        # the host repair backstop may not silently become the real solver
        assert res.pre_repair_violations == 0
        assert res.moves_repaired == 0

    def test_config3_anti_affinity(self):
        # BASELINE config 3 shape (scaled down for CPU): port/volume
        # anti-affinity constraints
        pt = synthetic_problem(200, 20, seed=1, port_fraction=0.4,
                               volume_fraction=0.2)
        res = solve(pt, chains=4, steps=300, seed=1)
        assert res.feasible, res.stats
        assert res.moves_repaired == 0, "repair backstop did the real work"

    def test_multi_tenant(self):
        # BASELINE config 4 shape (scaled): tenancy eligibility blocks
        pt = synthetic_problem(150, 15, seed=2, n_tenants=4)
        res = solve(pt, chains=4, steps=300, seed=2)
        assert res.feasible, res.stats
        assert res.moves_repaired == 0, "repair backstop did the real work"

    def test_warm_start_reschedule(self):
        # BASELINE config 5 shape: node churn → warm re-solve
        pt = synthetic_problem(100, 10, seed=3)
        res = solve(pt, chains=4, steps=300, seed=3)
        assert res.feasible
        # kill a node; services there must move, others should mostly stay
        dead = int(np.bincount(res.assignment, minlength=pt.N).argmax())
        pt.node_valid[dead] = False
        pt.eligible[:, dead] = False
        res2 = solve(pt, chains=4, steps=300, seed=4,
                     init_assignment=res.assignment)
        assert res2.feasible, res2.stats
        assert not (res2.assignment == dead).any()
        moved = (res2.assignment != res.assignment).mean()
        assert moved < 0.6  # warm start keeps most placements
        # warm path checks the adaptive exit every warm_block sweeps
        # (default 1 since r5's best-ever tracking made the block purely a
        # latency knob), so it stops at the first sweep that has SEEN
        # feasibility — a handful here (13/100 services displaced; large
        # fleets with proportionally smaller churn exit in 1-2, see bench
        # reschedule). WHICH sweep that is depends on the proposal draws:
        # over seeds 4..23 it ranges 2..11 on jax 0.9.0's default stream
        # (jax_threefry_partitionable=True since jax 0.5; median 3) and
        # 3..11 on the pre-0.5 stream (median 7) — same exit logic, same
        # tail. The old `<= 8` on seed 4 alone pinned one draw (4 sweeps
        # before 0.5, 11 now), so bound the MEDIAN over five seeds by it
        # and every run by an order of magnitude under the 300 budget.
        sweeps = [res2.steps] + [
            solve(pt, chains=4, steps=300, seed=s,
                  init_assignment=res.assignment).steps
            for s in range(5, 9)]
        assert min(sweeps) >= 1, sweeps
        assert sorted(sweeps)[len(sweeps) // 2] <= 8, sweeps
        assert max(sweeps) <= 30, sweeps

    def test_warm_block_exits_earlier_than_cold_block(self):
        pt = synthetic_problem(100, 10, seed=3)
        res = solve(pt, chains=4, steps=300, seed=3)
        dead = int(np.bincount(res.assignment, minlength=pt.N).argmax())
        pt.node_valid[dead] = False
        pt.eligible[:, dead] = False
        fine = solve(pt, chains=4, steps=300, seed=4,
                     init_assignment=res.assignment, warm_block=1)
        coarse = solve(pt, chains=4, steps=300, seed=4,
                       init_assignment=res.assignment, warm_block=64,
                       anneal_block=64)
        assert fine.feasible and coarse.feasible
        assert fine.steps < coarse.steps
        # both must produce a fully valid placement despite the early exit
        assert not (fine.assignment == dead).any()

    def test_spread_beats_random_balance(self):
        pt = synthetic_problem(120, 12, seed=7)
        res = solve(pt, chains=4, steps=500, seed=7)
        loads = np.zeros((pt.N, 3))
        np.add.at(loads, res.assignment, pt.demand)
        util = loads[:, 0] / pt.capacity[:, 0]
        assert res.feasible
        assert util.std() < 0.25  # spread strategy balances cpu

    def test_solve_is_deterministic_given_seed(self):
        pt = synthetic_problem(60, 6, seed=8)
        r1 = solve(pt, chains=2, steps=200, seed=9)
        r2 = solve(pt, chains=2, steps=200, seed=9)
        assert np.array_equal(r1.assignment, r2.assignment)


class TestMeshSharding:
    def test_chains_sharded_over_mesh(self):
        # 8 virtual CPU devices from conftest XLA_FLAGS
        devices = jax.devices()
        assert len(devices) == 8, "conftest should provide 8 CPU devices"
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devices), ("chains",))
        pt = synthetic_problem(80, 8, seed=10)
        res = solve(pt, chains=8, steps=200, seed=10, mesh=mesh)
        assert res.feasible, res.stats


class TestBatchedGreedy:
    """greedy_place_batched: the accelerator-shaped seed (sequential depth
    ceil(S/256) instead of S). It may leave a small best-effort tail of
    violations; the anneal must then still reach feasibility on its own."""

    def test_near_feasible_seed(self):
        from fleetflow_tpu.solver import greedy_place_batched
        pt = synthetic_problem(1000, 100, seed=0, n_tenants=8,
                               port_fraction=0.2, volume_fraction=0.1)
        prob = prepare_problem(pt)
        order = jnp.asarray(placement_order(pt.demand, pt.dep_depth,
                                            np.asarray(prob.conflict_ids)))
        a = np.asarray(greedy_place_batched(prob, order))
        assert ((a >= 0) & (a < pt.N)).all(), "every service must be placed"
        stats = verify(pt, a)
        # tail tolerance: < 5% of services on violating placements
        assert stats["total"] < 50, stats

    def test_solve_with_batched_seed_is_feasible(self):
        pt = synthetic_problem(300, 30, seed=4, n_tenants=4,
                               port_fraction=0.2, volume_fraction=0.1)
        res = solve(pt, chains=4, steps=300, seed=4, seed_impl="batched")
        assert res.feasible, res.stats
        assert res.pre_repair_violations == 0, \
            "anneal must clean up the batched seed tail on-device"
        assert res.moves_repaired == 0

    def test_matches_scan_quality_roughly(self):
        # soft score of batched seed after solve should be in the same
        # ballpark as the scan seed after solve (no quality cliff)
        pt = synthetic_problem(200, 20, seed=5)
        r_scan = solve(pt, chains=2, steps=200, seed=5, seed_impl="scan")
        r_batched = solve(pt, chains=2, steps=200, seed=5, seed_impl="batched")
        assert r_scan.feasible and r_batched.feasible
        # sign-safe "same ballpark" bound (soft can be negative under pack)
        assert r_batched.soft <= r_scan.soft + max(abs(r_scan.soft) * 0.5, 1.0)

    @pytest.mark.parametrize("strategy", [PlacementStrategy.SPREAD_ACROSS_POOL,
                                          PlacementStrategy.PACK_INTO_DEDICATED,
                                          PlacementStrategy.FILL_LOWEST])
    def test_batched_seed_small_tail_any_strategy(self, strategy):
        # pack/fill herd by design; the rank grouping must still keep the
        # best-effort tail small enough for the anneal to clean up
        from fleetflow_tpu.solver import greedy_place_batched
        pt = synthetic_problem(500, 50, seed=6, n_tenants=4,
                               port_fraction=0.2, volume_fraction=0.1,
                               strategy=strategy)
        prob = prepare_problem(pt)
        order = jnp.asarray(placement_order(pt.demand, pt.dep_depth,
                                            np.asarray(prob.conflict_ids)))
        a = np.asarray(greedy_place_batched(prob, order))
        stats = verify(pt, a)
        assert stats["total"] < 40, (strategy, stats)

    def test_solve_batched_seed_pack_feasible(self):
        pt = synthetic_problem(300, 30, seed=7, n_tenants=4,
                               strategy=PlacementStrategy.PACK_INTO_DEDICATED)
        res = solve(pt, chains=4, steps=300, seed=7, seed_impl="batched")
        assert res.feasible, res.stats
        assert res.pre_repair_violations == 0

    def test_solve_rejects_bad_seed_impl(self):
        pt = synthetic_problem(50, 5, seed=8)
        with pytest.raises(ValueError, match="seed_impl"):
            solve(pt, chains=2, steps=10, seed=8, seed_impl="ffd")

    def test_solve_with_native_seed_is_feasible(self):
        # VERDICT r2 item 5: the host C++ FFD is the violation-free floor
        # of the CPU fallback; the anneal on top must preserve feasibility
        # (winner-per-target sweeps) and never need the repair backstop.
        from fleetflow_tpu.native.lib import available
        if not available():
            pytest.skip("libffnative.so not built")
        pt = synthetic_problem(300, 30, seed=4, n_tenants=4,
                               port_fraction=0.2, volume_fraction=0.1)
        res = solve(pt, chains=2, steps=64, seed=4, seed_impl="native")
        assert res.feasible, res.stats
        assert res.pre_repair_violations == 0
        assert res.moves_repaired == 0

    def test_default_seed_on_cpu_is_native(self, monkeypatch):
        # The CPU fallback auto-picks the native seed when the library is
        # present (tests always run on the forced-CPU platform). Assert the
        # native placer was actually invoked, not just that solve worked.
        import fleetflow_tpu.native.lib as nlib
        if not nlib.available():
            pytest.skip("libffnative.so not built")
        calls = []
        real = nlib.native_place

        def spy(*a, **k):
            calls.append(1)
            return real(*a, **k)

        monkeypatch.setattr(nlib, "native_place", spy)
        pt = synthetic_problem(120, 12, seed=9, port_fraction=0.2)
        res = solve(pt, chains=2, steps=32, seed=9)   # seed_impl=None
        assert calls, "auto-pick on CPU must route through native_place"
        assert res.feasible, res.stats
        assert res.pre_repair_violations == 0


    def test_default_seed_on_cpu_is_partitioned_at_fleet_scale(self, monkeypatch):
        # Past S*N >= 1e6 the CPU auto-pick switches to the partitioned
        # FFD (r5: 82 -> 22 ms at 10k x 1k, equal soft). Assert the
        # partitioned path actually ran and the solve stayed clean.
        import fleetflow_tpu.native.lib as nlib
        import fleetflow_tpu.solver.greedy as greedy
        if not nlib.available():
            pytest.skip("libffnative.so not built")
        calls = []
        real = greedy.partitioned_seed

        def spy(pt_, parts):
            calls.append(parts)
            return real(pt_, parts)

        monkeypatch.setattr(greedy, "partitioned_seed", spy)
        pt = synthetic_problem(2000, 500, seed=10, port_fraction=0.2)
        res = solve(pt, chains=1, steps=64, seed=10)   # seed_impl=None
        assert calls == [4], "fleet-scale auto-pick must partition x4"
        assert res.feasible, res.stats
        assert res.pre_repair_violations == 0


class TestCarriedStateInvariants:
    """The early exit + chain ranking trust the anneal's incrementally
    carried ChainState. These tests pin the invariant: after any number of
    sweeps, the carried load/used/coloc/topo equal a from-scratch rebuild,
    and state_violation_stats/state_soft_score equal the exact kernels."""

    @staticmethod
    def _stage(kind: str):
        import dataclasses
        pt = synthetic_problem(120, 12, seed=3, n_tenants=3,
                               port_fraction=0.3, volume_fraction=0.2)
        if kind == "spread":
            # three zones, every move prices the skew term and the topo
            # counts ride the carried state
            pt = dataclasses.replace(
                pt, node_topology=np.arange(pt.N, dtype=np.int32) % 3,
                max_skew=4)
        elif kind == "coloc":
            # 30 co-location groups of three: the coloc occupancy plane
            # and its soft reward ride the carried state
            coloc = np.full((pt.S, 1), -1, np.int32)
            coloc[:90, 0] = np.arange(90) // 3
            pt = dataclasses.replace(pt, coloc_ids=coloc)
        return pt

    @pytest.mark.parametrize("kind", ["plain", "spread", "coloc"])
    def test_state_matches_rebuild_and_kernels(self, kind):
        from functools import partial

        from fleetflow_tpu.solver.anneal import (
            _batched_step, chain_states_from_assignment,
            state_soft_score, state_violation_stats)
        from fleetflow_tpu.solver.api import make_chain_inits
        from fleetflow_tpu.solver.kernels import soft_score, violation_stats

        pt = self._stage(kind)
        prob = prepare_problem(pt)
        key = jax.random.PRNGKey(0)
        C, steps = 3, 40
        inits = make_chain_inits(
            prob, jnp.zeros((pt.S,), jnp.int32), C, key)
        states = jax.vmap(partial(chain_states_from_assignment, prob))(inits)
        decay = 1e-3 ** (1.0 / (steps - 1))

        # the reference loop: the anneal's own step, every sweep applied,
        # no early exit and no best-ever selection in the way
        def sweep(carry, i):
            states, keys = carry
            keys = jax.vmap(lambda k: jax.random.fold_in(k, i))(keys)
            temp = decay ** i.astype(jnp.float32)
            states, _ = jax.vmap(
                lambda st, k: _batched_step(prob, st, k, temp, 64))(
                    states, keys)
            return (states, keys), None

        (states, _), _ = jax.lax.scan(
            sweep, (states, jax.random.split(key, C)),
            jnp.arange(steps, dtype=jnp.int32))

        for c in range(C):
            st = jax.tree.map(lambda x: x[c], states)
            rebuilt = chain_states_from_assignment(prob, st.assignment)
            for name, a, b in zip(st._fields, st, rebuilt):
                assert np.allclose(np.asarray(a), np.asarray(b)), (c, name)
            ks = violation_stats(prob, st.assignment)
            ss = state_violation_stats(prob, st)
            for k in ks:
                assert float(ks[k]) == pytest.approx(float(ss[k])), (c, k)
            assert float(soft_score(prob, st.assignment)) == pytest.approx(
                float(state_soft_score(prob, st)), abs=1e-4), c

    def test_adaptive_exits_early_on_easy_instance(self):
        pt = synthetic_problem(80, 20, seed=4)
        res = solve(pt, chains=2, steps=128, seed=4)
        assert res.feasible
        assert res.steps <= 64, f"expected early exit, ran {res.steps} sweeps"

    def test_short_budget_reaches_long_budget_violations(self):
        """The exit keys on seen feasibility, not on the budget: a quarter
        of the sweeps lands the device's winner on the same violation
        count as the full budget, with nothing left for the host."""
        pt = synthetic_problem(200, 20, seed=5, n_tenants=4,
                               port_fraction=0.3)
        r_long = solve(pt, chains=4, steps=128, seed=5)
        r_short = solve(pt, chains=4, steps=32, seed=5)
        assert r_short.pre_repair_violations == r_long.pre_repair_violations
        assert r_short.violations == r_long.violations == 0
        assert r_short.moves_repaired == r_long.moves_repaired == 0

    def test_best_ever_tracking_is_monotone_in_block(self):
        """More annealing can only help (r5): the adaptive anneal returns
        each chain's best-ever state, so a larger block — which runs MORE
        sweeps past the first feasible point before its exit check — must
        never return a worse placement than a smaller one. The sweep RNG
        is folded by sweep index and the temperature schedule is fixed
        against max_steps, so the block=8 run's visited states are a
        superset of the block=2 run's; with both feasible, the returned
        soft must be <=. Pre-fix the 8-sweep run RETURNED soft 1.3714
        where the 2-sweep run returned 1.3390 on the 1k x 100 instance
        (the final Metropolis state, not the best visited one)."""
        pt = synthetic_problem(400, 40, seed=6, n_tenants=4,
                               port_fraction=0.2)
        r2 = solve(pt, chains=2, steps=32, seed=7, anneal_block=2)
        r8 = solve(pt, chains=2, steps=32, seed=7, anneal_block=8)
        assert r2.violations == 0 and r8.violations == 0
        assert int(r8.steps) >= int(r2.steps)
        # tolerance above float32 carried-state drift: winners are
        # argmin'd on incrementally-accumulated costs while .soft is an
        # exact recompute, so near-equal chains can invert by ~1e-5
        assert r8.soft <= r2.soft + 5e-4, (r8.soft, r2.soft)
