"""Mesh-resident sharded state (solver/sharded.ShardedResident +
solve_sharded): the pod-scale warm path holds the same contracts the
single-chip resident path proved in tests/test_resident.py — churn applied
as on-mesh deltas is bit-identical to a cold sharded restaging, warm
re-solves reuse one executable and run under the disallow transfer guard —
plus the parallel-tempering additions: the Metropolis replica-exchange
criterion satisfies detailed balance, and a 2-lane mesh exchange is
deterministic down to the bit.

One fixed shape (73x12, padded tier 80, divisible over the 4-wide service
axis) keeps the whole module to a bounded compile count; warm and cold
solve_sharded dispatches share ONE executable because n_real is traced and
every static arg (steps/mesh/block/exchange_every) is pinned.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.solver import prepare_problem
from fleetflow_tpu.solver.repair import verify
from fleetflow_tpu.solver.resident import ProblemDelta
from fleetflow_tpu.solver.sharded import (REPLICA_AXIS, SVC_AXIS,
                                          ShardedResident, anneal_sharded,
                                          pad_problem, solve_sharded,
                                          tempering_mesh,
                                          tempering_swap_accept,
                                          tempering_swap_delta)

STEPS = 16


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices, have {len(jax.devices())}")


def _churn_step(pt, rng):
    """One random churn event (same event family as tests/test_resident's
    _churn_step): a validity flip + a capacity drift + a demand drift on a
    few rows. Returns (new pt sharing untouched arrays, matching delta)."""
    valid = pt.node_valid.copy()
    j = int(rng.integers(0, pt.N))
    valid[j] = ~valid[j]
    if not valid.any():
        valid[j] = True
    cap = pt.capacity.copy()
    cap[int(rng.integers(0, pt.N))] *= float(rng.uniform(0.9, 1.2))
    rows = rng.choice(pt.S, size=3, replace=False).astype(np.int32)
    dem = pt.demand.copy()
    dem[rows] = (dem[rows] * rng.uniform(0.5, 1.5)).astype(dem.dtype)
    nxt = dataclasses.replace(pt, node_valid=valid, capacity=cap, demand=dem)
    delta = ProblemDelta(node_valid=valid, capacity=cap,
                         demand_rows=(rows, dem[rows]))
    return nxt, delta


class TestShardedDeltaEquivalence:
    """Property: a churn sequence applied via on-mesh deltas == a cold
    sharded restaging, bit for bit — padded device tensors AND final
    assignments (the tests/test_resident.py contract at pod scale)."""

    @pytest.mark.parametrize("seed", range(3))
    def test_churn_sequence_equivalence(self, seed):
        _need_devices(8)
        rng = np.random.default_rng(seed)
        pt = synthetic_problem(73, 12, seed=seed, port_fraction=0.3,
                               volume_fraction=0.2)
        mesh = tempering_mesh(2, 4)
        rp = ShardedResident(pt, mesh=mesh)
        base = solve_sharded(pt, resident=rp, steps=STEPS, seed=seed)
        prev_cold = base.assignment
        for step in range(3):
            pt, delta = _churn_step(pt, rng)
            assert rp.compatible(pt, delta)
            rp.apply_delta(pt, delta)
            a = solve_sharded(pt, resident=rp, resident_warm=True,
                              steps=STEPS, seed=100 + step)
            # cold restage: a FRESH mesh staging of the mutated tensors,
            # seeded with the same previous assignment, same solve policy
            # — only the staging differs, which is the property under test
            rp2 = ShardedResident(pt, mesh=mesh)
            rp2.adopt_host(prev_cold, pt.node_valid, warm=False)
            b = solve_sharded(pt, resident=rp2, resident_warm=True,
                              steps=STEPS, seed=100 + step)
            prev_cold = b.assignment
            assert np.array_equal(a.assignment, b.assignment), \
                f"delta-staged solve diverged from cold restage at {step}"
            # identical padded mesh-sharded tensors
            for f in dataclasses.fields(rp.prob):
                va, vb = getattr(rp.prob, f.name), getattr(rp2.prob, f.name)
                if hasattr(va, "shape"):
                    assert np.array_equal(np.asarray(va), np.asarray(vb)), \
                        f"mesh-resident tensor {f.name} drifted at {step}"
            assert int(rp.prob.n_real) == pt.S
            assert verify(pt, a.assignment)["total"] == a.stats["total"]

    def test_warm_resolves_reuse_one_executable_under_guard(self,
                                                            monkeypatch):
        """The steady-state loop: every warm burst after the first reuses
        ONE sharded executable (traced n_real — tier drift cannot
        recompile) and completes under jax.transfer_guard('disallow')."""
        _need_devices(8)
        rng = np.random.default_rng(11)
        pt = synthetic_problem(73, 12, seed=11, port_fraction=0.3)
        mesh = tempering_mesh(2, 4)
        rp = ShardedResident(pt, mesh=mesh)
        solve_sharded(pt, resident=rp, steps=STEPS, seed=11)
        # first warm burst may compile the warm variant (it should not —
        # n_real and t0 are traced — but the pin is the loop after it)
        pt, delta = _churn_step(pt, rng)
        rp.apply_delta(pt, delta)
        solve_sharded(pt, resident=rp, resident_warm=True, steps=STEPS,
                      seed=12)
        monkeypatch.setenv("FLEET_TRANSFER_GUARD", "disallow")
        cache_before = anneal_sharded._cache_size()
        for step in range(3):
            pt, delta = _churn_step(pt, rng)
            rp.apply_delta(pt, delta)
            r = solve_sharded(pt, resident=rp, resident_warm=True,
                              steps=STEPS, seed=13 + step)
            assert r.tempering["replicas"] == 2
        assert anneal_sharded._cache_size() == cache_before, \
            "warm sharded re-solves recompiled"


class TestShardedPackedParity:
    """ISSUE 13 property at pod scale: the packed layout (bit-packed
    eligibility shards, absent preference plane) solves bit-identically
    to the dense layout through the mesh-sharded warm path."""

    @pytest.mark.parametrize("seed", range(2))
    def test_sharded_warm_path_matches_dense(self, seed, monkeypatch):
        _need_devices(8)
        pt0 = synthetic_problem(72, 12, seed=seed, port_fraction=0.3,
                                volume_fraction=0.2, n_tenants=2)
        mesh = tempering_mesh(2, 4)
        runs = {}
        for packed in (True, False):
            monkeypatch.setenv("FLEET_PACKED", "1" if packed else "0")
            rng = np.random.default_rng(seed)   # identical churn stream
            pt = pt0
            rp = ShardedResident(pt, mesh=mesh)
            assert (np.asarray(rp.prob.eligible).dtype
                    == (np.uint32 if packed else np.bool_))
            assert (rp.prob.preferred is None) == packed
            base = solve_sharded(pt, resident=rp, steps=STEPS, seed=seed)
            seq = [(base.assignment.copy(), base.stats["total"],
                    base.soft)]
            for step in range(2):
                pt, delta = _churn_step(pt, rng)
                assert rp.compatible(pt, delta)
                rp.apply_delta(pt, delta)
                r = solve_sharded(pt, resident=rp, resident_warm=True,
                                  steps=STEPS, seed=100 + step)
                seq.append((r.assignment.copy(), r.stats["total"],
                            r.soft))
            runs[packed] = seq
        for i, ((a, va, sa), (b, vb, sb)) in enumerate(
                zip(runs[True], runs[False])):
            assert np.array_equal(a, b), \
                f"packed/dense sharded assignments diverged at step {i}"
            assert va == vb and sa == sb, \
                f"packed/dense sharded stats diverged at step {i}"


class TestTemperingCriterion:
    """The Metropolis replica-exchange criterion: detailed balance by
    construction, equal temperatures a distributional no-op, and ~50%
    acceptance between equal-energy-distribution lanes at a wide gap."""

    def test_detailed_balance_identity(self):
        rng = np.random.default_rng(0)
        e_a = jnp.asarray(rng.normal(10, 3, 256), jnp.float32)
        e_b = jnp.asarray(rng.normal(10, 3, 256), jnp.float32)
        b_a, b_b = jnp.float32(2.0), jnp.float32(0.5)
        d = tempering_swap_delta(e_a, e_b, b_a, b_b)
        # antisymmetry: the reverse exchange proposes the negated delta
        assert np.allclose(np.asarray(d),
                           -np.asarray(tempering_swap_delta(e_b, e_a,
                                                            b_a, b_b)))
        # detailed balance: p(swap)/p(unswap) == the Boltzmann weight
        # ratio exp((β_a − β_b)(E_a − E_b)), with p = min(1, exp(±d))
        p_fwd = np.minimum(1.0, np.exp(np.asarray(d, np.float64)))
        p_rev = np.minimum(1.0, np.exp(-np.asarray(d, np.float64)))
        assert np.allclose(p_fwd / p_rev, np.exp(np.asarray(d, np.float64)),
                           rtol=1e-6)

    def test_equal_temperature_always_accepts(self):
        """At β_a == β_b the swap is a distributional no-op and the
        criterion accepts every proposal (log-ratio is exactly 0)."""
        rng = np.random.default_rng(1)
        e_a = jnp.asarray(rng.normal(0, 5, 512), jnp.float32)
        e_b = jnp.asarray(rng.normal(0, 5, 512), jnp.float32)
        u = jnp.asarray(rng.uniform(0, 1, 512), jnp.float32)
        acc = tempering_swap_accept(e_a, e_b, jnp.float32(1.5),
                                    jnp.float32(1.5), u)
        assert bool(np.all(np.asarray(acc)))

    def test_wide_gap_iid_energies_accepts_about_half(self):
        """Between lanes whose energy distributions coincide, a wide β gap
        accepts ~the favorable-sign half: acceptance → 50% (the detailed-
        balance sanity the ISSUE pins — a criterion that accepted all or
        none would not be sampling the joint distribution)."""
        rng = np.random.default_rng(2)
        n = 20_000
        e_a = jnp.asarray(rng.normal(100, 10, n), jnp.float32)
        e_b = jnp.asarray(rng.normal(100, 10, n), jnp.float32)
        u = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
        acc = tempering_swap_accept(e_a, e_b, jnp.float32(50.0),
                                    jnp.float32(0.02), u)
        frac = float(np.mean(np.asarray(acc)))
        assert 0.45 < frac < 0.55, f"acceptance {frac} not ~50%"


class TestExchangeDeterminism:
    """A tempered 2-lane mesh run is deterministic: same key, same
    problem => bit-identical winner and identical swap counters. The
    instance demands more than the fleet holds, so no state is feasible,
    the early exit never fires and every block's exchange round runs."""

    @staticmethod
    def _overfull():
        pt = synthetic_problem(64, 10, seed=5, port_fraction=0.2)
        # the generator sizes capacity at ~1.5x the demand: half of it
        # holds ~77%, so every placement overloads some node
        pt = dataclasses.replace(pt, capacity=pt.capacity * 0.5)
        assert (pt.demand.sum(axis=0) > pt.capacity.sum(axis=0)).all()
        return pt

    def test_two_lane_exchange_is_deterministic(self):
        _need_devices(2)
        pt = self._overfull()
        prob = prepare_problem(pt)
        padded, orig = pad_problem(prob, 1)
        mesh = tempering_mesh(2, 1)
        assert mesh.shape == {REPLICA_AXIS: 2, SVC_AXIS: 1}
        init = jnp.zeros((padded.S,), jnp.int32)
        kw = dict(steps=STEPS, mesh=mesh, block=4,
                  n_real=orig, return_stats=True)
        r1 = anneal_sharded(padded, init, jax.random.PRNGKey(9), **kw)
        r2 = anneal_sharded(padded, init, jax.random.PRNGKey(9), **kw)
        assert np.array_equal(np.asarray(r1.assignment),
                              np.asarray(r2.assignment))
        assert int(r1.sweeps) == STEPS            # no exit: every block ran
        assert float(r1.violations) > 0
        # exchanges actually ran, and their outcome is pinned by the key
        assert int(r1.swap_attempts) > 0
        assert int(r1.swap_attempts) == int(r2.swap_attempts)
        assert int(r1.swap_accepts) == int(r2.swap_accepts)
        # the winner is replica-replicated: exact host verification holds
        a = np.asarray(r1.assignment)[:orig]
        assert verify(pt, a)["total"] == r1.violations

    def test_sparse_exchange_cadence_still_trades(self):
        """exchange_every > 1 routes the round through lax.cond (the off
        blocks skip the collectives entirely) and the pairing parity
        advances per ROUND — a 2-lane ladder must still trade."""
        _need_devices(2)
        pt = self._overfull()
        prob = prepare_problem(pt)
        padded, orig = pad_problem(prob, 1)
        mesh = tempering_mesh(2, 1)
        init = jnp.zeros((padded.S,), jnp.int32)
        kw = dict(steps=STEPS, mesh=mesh, block=4,
                  n_real=orig, exchange_every=2, return_stats=True)
        r1 = anneal_sharded(padded, init, jax.random.PRNGKey(9), **kw)
        r2 = anneal_sharded(padded, init, jax.random.PRNGKey(9), **kw)
        # 4 blocks at cadence 2 -> at most 2 rounds, at least one on the
        # even parity where the single lane pair exists
        assert 0 < int(r1.swap_attempts) <= 2
        assert np.array_equal(np.asarray(r1.assignment),
                              np.asarray(r2.assignment))


class TestShardedRouting:
    """api.solve / TpuSolverScheduler route to the mesh-resident sharded
    path under FLEET_SHARDED=1, and the scheduler's slot matching keys on
    the mesh so a routing flip mid-life can never hand a sharded staging
    to the single-chip solve."""

    def test_scheduler_routes_and_reuses_delta(self, monkeypatch):
        _need_devices(8)
        from fleetflow_tpu.obs.metrics import REGISTRY
        from fleetflow_tpu.sched import TpuSolverScheduler
        m = REGISTRY.get("fleet_solver_sharded_solves_total")
        core = REGISTRY.get("fleet_solver_solves_total")
        monkeypatch.setenv("FLEET_SHARDED", "1")
        pt = synthetic_problem(73, 12, seed=31, port_fraction=0.3)
        sched = TpuSolverScheduler(chains=1, steps=STEPS)
        before_cold = m.value(outcome="cold")
        before_delta = m.value(outcome="delta")
        before_core = core.value(backend="cpu", warm="false")
        p = sched.place(pt)
        assert p.raw.shape[0] == pt.S
        assert m.value(outcome="cold") == before_cold + 1
        # the CORE solver families keep reflecting pod-scale solves
        assert core.value(backend="cpu", warm="false") == before_core + 1
        valid = pt.node_valid.copy()
        valid[3] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        r = sched.reschedule(pt2, delta=ProblemDelta(node_valid=valid))
        assert r.raw.shape[0] == pt.S
        dead = pt.node_names[3]
        assert not [s for s, n in r.assignment.items() if n == dead]
        assert m.value(outcome="delta") == before_delta + 1

    def test_routing_flip_cannot_reuse_sharded_slot(self, monkeypatch):
        _need_devices(8)
        from fleetflow_tpu.obs.metrics import REGISTRY
        from fleetflow_tpu.sched import TpuSolverScheduler
        m = REGISTRY.get("fleet_solver_sharded_solves_total")
        monkeypatch.setenv("FLEET_SHARDED", "1")
        pt = synthetic_problem(73, 12, seed=32, port_fraction=0.3)
        sched = TpuSolverScheduler(chains=1, steps=STEPS)
        sched.place(pt)
        # flip the route off: the sharded slot must NOT serve the
        # single-chip path — a fresh single-chip staging solves instead
        monkeypatch.setenv("FLEET_SHARDED", "0")
        before = m.value(outcome="cold") + m.value(outcome="delta")
        valid = pt.node_valid.copy()
        valid[2] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        r = sched.reschedule(pt2, delta=ProblemDelta(node_valid=valid))
        assert r.raw.shape[0] == pt.S
        assert m.value(outcome="cold") + m.value(outcome="delta") == before

    def test_api_solve_routes_above_threshold(self, monkeypatch):
        _need_devices(8)
        from fleetflow_tpu.solver import solve
        monkeypatch.setenv("FLEET_SHARDED", "1")
        pt = synthetic_problem(73, 12, seed=33)
        res = solve(pt, steps=STEPS, seed=33)
        assert res.tempering is not None
        assert res.tempering["replicas"] == 2
        assert res.assignment.shape[0] == pt.S
        assert verify(pt, res.assignment)["total"] == res.stats["total"]
        # an explicit staging kwarg pins the call to the single-chip path
        from fleetflow_tpu.solver.resident import ResidentProblem
        rp = ResidentProblem(pt)
        res2 = solve(pt, prob=rp.prob, resident=rp, steps=STEPS, seed=33,
                     bucket=rp.bucket)
        assert res2.tempering is None


class TestShardedResultOwnership:
    """Regression for the solve_sharded fetch site (the PR 14 bug class
    on the pod-scale path): the winner came off the mesh via
    `jax.device_get(tuple(res))` and was sliced with np.asarray — on the
    CPU backend that is a zero-copy VIEW of the very buffer `rp.adopt`
    had just made the mesh-resident seed. The next warm sharded dispatch
    DONATES that buffer, clobbering every retained result in place. The
    fix forces `np.array(..., copy=True)` before the slice; this test
    pins both legs of the contract: the returned array OWNS its memory
    (on a 1x1 mesh the assembled fetch is single-shard, so asarray would
    hand back the raw zero-copy view — the mutation-sensitive case) and
    results fetched before churn stay bit-identical through later warm
    dispatches."""

    @pytest.mark.parametrize("dims", [(1, 1), (2, 4)])
    def test_result_survives_later_warm_dispatches(self, dims):
        _need_devices(8)
        rng = np.random.default_rng(14)
        pt = synthetic_problem(73, 12, seed=14, port_fraction=0.3,
                               volume_fraction=0.2)
        mesh = tempering_mesh(*dims)
        rp = ShardedResident(pt, mesh=mesh)
        base = solve_sharded(pt, resident=rp, steps=STEPS, seed=14)
        kept = base.assignment
        # ownership: the slice's base must be a host-owned copy, never a
        # wrapper over the device buffer rp.adopt just made the warm seed
        assert kept.base is None or kept.base.flags["OWNDATA"], \
            "solve_sharded returned a view of the mesh-resident seed"
        pinned = kept.copy()
        for step in range(3):
            pt, delta = _churn_step(pt, rng)
            rp.apply_delta(pt, delta)
            solve_sharded(pt, resident=rp, resident_warm=True,
                          steps=STEPS, seed=140 + step)
        assert np.array_equal(kept, pinned), \
            "sharded result clobbered in place by a later warm dispatch" \
            " (donated device_get view — the PR 14 aliasing class)"
