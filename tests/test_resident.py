"""Device-resident fleet state (solver/resident.py): delta-staged solves
are equivalent to cold restaging, the warm path moves no problem tensors
across the host boundary (transfer-guard pinned), fused pre-repair replaces
the host pre-pass, and the scheduler's reuse/fallback decisions are
correct and counted.

The equivalence property is the PR's contract: apply a random churn
sequence BOTH ways — on-device deltas into the resident buffers vs a fresh
host staging of the mutated ProblemTensors — and the padded device tensors
AND the final assignments must be bit-identical (same seed, same fused
pipeline). One fixed shape keeps the sweep to a bounded compile count, the
same budget discipline as tests/test_buckets.py.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.sched import TpuSolverScheduler
from fleetflow_tpu.solver import (bucket_config, pad_problem_tiers,
                                  prepare_problem, solve)
from fleetflow_tpu.solver.api import _refine
from fleetflow_tpu.solver.repair import verify
from fleetflow_tpu.solver.resident import ProblemDelta, ResidentProblem


def _churn_step(pt, rng):
    """One random churn event: a validity flip + a capacity drift +
    a demand drift on a few rows. Returns (new pt sharing untouched
    arrays, the matching ProblemDelta)."""
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


class TestDeltaEquivalence:
    """Property: delta staging == cold restaging, bit for bit."""

    @pytest.mark.parametrize("seed", range(6))
    def test_churn_sequence_equivalence(self, seed):
        rng = np.random.default_rng(seed)
        pt = synthetic_problem(73, 12, seed=seed, port_fraction=0.3,
                               volume_fraction=0.2)
        rp = ResidentProblem(pt)
        cold = solve(pt, seed=seed, steps=16, bucket=True)
        res = solve(pt, prob=rp.prob, resident=rp, seed=seed, steps=16,
                    bucket=True)
        assert np.array_equal(res.assignment, cold.assignment)
        prev_cold = cold.assignment
        for step in range(4):
            pt, delta = _churn_step(pt, rng)
            rp.apply_delta(pt, delta)
            a = solve(pt, prob=rp.prob, resident=rp, resident_warm=True,
                      seed=100 + step, steps=16, bucket=True)
            # cold restage: a FRESH host staging of the mutated tensors,
            # seeded with the same previous assignment, same solve policy
            # — only the staging differs, which is the property under test
            rp2 = ResidentProblem(pt)
            rp2.adopt_host(prev_cold, pt.node_valid, warm=False)
            b = solve(pt, prob=rp2.prob, resident=rp2, resident_warm=True,
                      seed=100 + step, steps=16, bucket=True)
            prev_cold = b.assignment
            # identical final assignments on the real rows
            assert np.array_equal(a.assignment, b.assignment), \
                f"delta-staged solve diverged from cold restage at {step}"
            # identical padded device tensors
            probc, _ = pad_problem_tiers(prepare_problem(pt),
                                         bucket_config())
            for f in dataclasses.fields(rp.prob):
                va = getattr(rp.prob, f.name)
                vb = getattr(probc, f.name)
                if hasattr(va, "shape"):
                    assert np.array_equal(np.asarray(va), np.asarray(vb)), \
                        f"resident tensor {f.name} drifted at step {step}"
            assert int(rp.prob.n_real) == pt.S

    def test_arrival_activates_phantom_rows_on_device(self):
        """Arrivals ride the delta path: services appended within the
        padded tier (bringing no new constraint ids) write into phantom
        rows on device — same padded tensors and same final assignment as
        a cold restage of the grown fleet."""
        k = 2
        pt = synthetic_problem(73, 12, seed=4, port_fraction=0.3)
        rp = ResidentProblem(pt)
        solve(pt, prob=rp.prob, resident=rp, seed=4, steps=16, bucket=True)

        S2 = pt.S + k
        names = [f"arrival{i}" for i in range(k)]
        grow = lambda a: np.concatenate(
            [a, np.full((k, a.shape[1]), -1, dtype=a.dtype)])
        dem_new = np.full((k, pt.demand.shape[1]), 0.01,
                          dtype=pt.demand.dtype)
        elig_new = np.ones((k, pt.N), dtype=bool)
        pt2 = dataclasses.replace(
            pt,
            service_names=pt.service_names + names,
            demand=np.concatenate([pt.demand, dem_new]),
            eligible=np.concatenate([pt.eligible, elig_new]),
            dep_adj=np.pad(pt.dep_adj, ((0, k), (0, k))),
            dep_depth=np.concatenate(
                [pt.dep_depth, np.zeros(k, pt.dep_depth.dtype)]),
            port_ids=grow(pt.port_ids), volume_ids=grow(pt.volume_ids),
            anti_ids=grow(pt.anti_ids), coloc_ids=grow(pt.coloc_ids),
            replica_of=pt.replica_of + names if pt.replica_of else
            pt.replica_of)
        rows = np.arange(pt.S, S2, dtype=np.int32)
        delta = ProblemDelta(demand_rows=(rows, dem_new),
                             eligible_rows=(rows, elig_new), n_real=S2)
        assert rp.compatible(pt2, delta)
        # richer arrivals cannot ride the delta: a delta missing the
        # arrivals' eligibility, or an arrival carrying a new constraint
        # id, falls back to cold staging
        assert not rp.compatible(
            pt2, ProblemDelta(demand_rows=(rows, dem_new), n_real=S2))
        pt3 = dataclasses.replace(pt2, port_ids=pt2.port_ids.copy())
        pt3.port_ids[-1, 0] = 0
        assert not rp.compatible(pt3, delta)
        rp.apply_delta(pt2, delta)
        seed_host = np.asarray(rp.assignment)[:S2]
        a = solve(pt2, prob=rp.prob, resident=rp, resident_warm=True,
                  seed=104, steps=16, bucket=True)
        assert a.assignment.shape[0] == S2
        assert a.feasible
        assert int(rp.prob.n_real) == S2
        # equivalence: a cold restage of the grown pt, same seed policy
        rp2 = ResidentProblem(pt2)
        rp2.adopt_host(seed_host, pt2.node_valid, warm=False)
        b = solve(pt2, prob=rp2.prob, resident=rp2, resident_warm=True,
                  seed=104, steps=16, bucket=True)
        assert np.array_equal(a.assignment, b.assignment)
        probc, _ = pad_problem_tiers(prepare_problem(pt2), bucket_config())
        for f in dataclasses.fields(rp.prob):
            va, vb = getattr(rp.prob, f.name), getattr(probc, f.name)
            if hasattr(va, "shape"):
                assert np.array_equal(np.asarray(va), np.asarray(vb)), \
                    f"resident tensor {f.name} drifted after arrival delta"

    def test_arrival_packed_row_scatter_restricted_mask(self):
        """The packed-row-scatter arrival path: an arrival whose
        eligibility mask is RESTRICTED (not all-True) lands on the
        resident plane as bit-packed words, and the warm solve honors the
        scattered restriction."""
        from fleetflow_tpu.solver.problem import pack_bool_rows

        pt = synthetic_problem(70, 12, seed=5)
        rp = ResidentProblem(pt)
        solve(pt, prob=rp.prob, resident=rp, seed=5, steps=16, bucket=True)
        k = 2
        S2 = pt.S + k
        names = [f"arrival{i}" for i in range(k)]
        grow = lambda a: np.concatenate(
            [a, np.full((k, a.shape[1]), -1, dtype=a.dtype)])
        dem_new = np.full((k, pt.demand.shape[1]), 0.01,
                          dtype=pt.demand.dtype)
        elig_new = np.zeros((k, pt.N), dtype=bool)
        elig_new[:, :5] = True          # arrivals pinned to the first 5
        pt2 = dataclasses.replace(
            pt,
            service_names=pt.service_names + names,
            demand=np.concatenate([pt.demand, dem_new]),
            eligible=np.concatenate([pt.eligible, elig_new]),
            dep_adj=np.pad(pt.dep_adj, ((0, k), (0, k))),
            dep_depth=np.concatenate(
                [pt.dep_depth, np.zeros(k, pt.dep_depth.dtype)]),
            port_ids=grow(pt.port_ids), volume_ids=grow(pt.volume_ids),
            anti_ids=grow(pt.anti_ids), coloc_ids=grow(pt.coloc_ids),
            replica_of=pt.replica_of + names if pt.replica_of else
            pt.replica_of)
        rows = np.arange(pt.S, S2, dtype=np.int32)
        delta = ProblemDelta(demand_rows=(rows, dem_new),
                             eligible_rows=(rows, elig_new), n_real=S2)
        assert rp.compatible(pt2, delta)
        rp.apply_delta(pt2, delta)
        # the scattered rows are the PACKED image of the bool masks
        got = np.asarray(rp.prob.eligible)[pt.S:S2]
        assert got.dtype == np.uint32
        assert np.array_equal(got, pack_bool_rows(elig_new))
        r = solve(pt2, prob=rp.prob, resident=rp, resident_warm=True,
                  seed=105, steps=64, bucket=True)
        assert r.feasible
        assert (r.assignment[pt.S:] < 5).all(), \
            "arrivals must obey the packed-row-scattered eligibility"

    def test_bounded_compiles_across_sequence(self):
        """The whole delta sequence reuses ONE fused-pipeline executable:
        every burst stays inside the shape tier."""
        rng = np.random.default_rng(7)
        pt = synthetic_problem(73, 12, seed=7, port_fraction=0.3)
        rp = ResidentProblem(pt)
        solve(pt, prob=rp.prob, resident=rp, seed=7, steps=16, bucket=True)
        # first warm solve compiles the warm/fused variant
        pt, delta = _churn_step(pt, rng)
        rp.apply_delta(pt, delta)
        solve(pt, prob=rp.prob, resident=rp, resident_warm=True, seed=8,
              steps=16, bucket=True)
        cache_before = _refine._cache_size()
        for step in range(3):
            pt, delta = _churn_step(pt, rng)
            rp.apply_delta(pt, delta)
            r = solve(pt, prob=rp.prob, resident=rp, resident_warm=True,
                      seed=9 + step, steps=16, bucket=True)
            assert r.fused_prerepair
        assert _refine._cache_size() == cache_before, \
            "warm delta re-solves recompiled the fused pipeline"


class TestPackedParity:
    """ISSUE 13 property: the packed problem layout (bit-packed uint32
    eligibility + absent preference plane) is numerically IDENTICAL to
    the dense layout — bit-identical final assignments and identical
    violation/soft stats — across the cold path and the resident-delta
    warm path, over N seeds. The packed plane is a pure re-encoding: the
    kernels unpack with shift/mask at each gather site, so the proposal
    stream, the Metropolis decisions, and every carried float are
    unchanged."""

    @pytest.mark.parametrize("seed", range(4))
    def test_cold_and_delta_paths_match_dense(self, seed, monkeypatch):
        pt0 = synthetic_problem(73, 12, seed=seed, port_fraction=0.3,
                                volume_fraction=0.2, n_tenants=2)
        runs = {}
        for packed in (True, False):
            monkeypatch.setenv("FLEET_PACKED", "1" if packed else "0")
            rng = np.random.default_rng(seed)   # identical churn stream
            pt = pt0
            rp = ResidentProblem(pt)
            assert (np.asarray(rp.prob.eligible).dtype
                    == (np.uint32 if packed else np.bool_))
            assert (rp.prob.preferred is None) == packed
            cold = solve(pt, prob=rp.prob, resident=rp, seed=seed,
                         steps=16, bucket=True)
            seq = [(cold.assignment.copy(), cold.violations, cold.soft)]
            for step in range(3):
                pt, delta = _churn_step(pt, rng)
                rp.apply_delta(pt, delta)
                r = solve(pt, prob=rp.prob, resident=rp,
                          resident_warm=True, seed=100 + step, steps=16,
                          bucket=True)
                seq.append((r.assignment.copy(), r.violations, r.soft))
            runs[packed] = seq
        for i, ((a, va, sa), (b, vb, sb)) in enumerate(
                zip(runs[True], runs[False])):
            assert np.array_equal(a, b), \
                f"packed/dense assignments diverged at step {i}"
            assert va == vb, f"violations diverged at step {i}"
            assert sa == sb, f"soft stats diverged at step {i}"


class TestTransferGuard:
    """The acceptance pin: a warm delta-staged reschedule completes under
    jax.transfer_guard('disallow') — zero host transfers of problem
    tensors or the seed assignment."""

    def test_warm_path_under_disallow_guard(self, monkeypatch):
        pt = synthetic_problem(97, 16, seed=9, port_fraction=0.2)
        sched = TpuSolverScheduler(chains=1, steps=64)
        first = sched.place(pt)
        assert first.feasible
        victim = int(np.bincount(first.raw, minlength=pt.N).argmax())
        valid = pt.node_valid.copy()
        valid[victim] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        monkeypatch.setenv("FLEET_TRANSFER_GUARD", "disallow")
        second = sched.reschedule(pt2, delta=ProblemDelta(node_valid=valid))
        assert second.feasible
        assert not np.any(np.asarray(second.raw) == victim)
        assert verify(pt2, second.raw)["total"] == 0
        # and again, proving the steady-state loop holds under the guard
        victim2 = int(np.bincount(second.raw, minlength=pt.N).argmax())
        valid2 = valid.copy()
        valid2[victim2] = False
        valid2[victim] = True
        pt3 = dataclasses.replace(pt, node_valid=valid2)
        third = sched.reschedule(pt3, delta=ProblemDelta(node_valid=valid2))
        assert third.feasible
        assert not np.any(np.asarray(third.raw) == victim2)

    def test_warm_timings_have_no_host_prerepair(self, monkeypatch):
        pt = synthetic_problem(60, 8, seed=3)
        sched = TpuSolverScheduler(chains=1, steps=64)
        base = sched.place(pt)
        victim = int(np.bincount(base.raw, minlength=pt.N).argmax())
        valid = pt.node_valid.copy()
        valid[victim] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        from fleetflow_tpu.solver import api as solver_api
        seen = {}
        orig = solver_api._solve

        def spy(pt_, **kw):
            r = orig(pt_, **kw)
            seen.update(r.timings_ms)
            seen["fused"] = r.fused_prerepair
            return r

        monkeypatch.setattr(solver_api, "_solve", spy)
        sched.reschedule(pt2, delta=ProblemDelta(node_valid=valid))
        assert "prerepair_ms" not in seen, \
            "warm resident path must not run host pre-repair"
        assert seen["fused"] is True
        assert "delta_stage_ms" in seen


class TestSchedulerReuse:
    def test_capacity_drift_rides_delta_not_restage(self):
        """The pre-resident identity cache restaged the whole problem on
        every capacity refresh; the resident layer must count it as delta
        reuse."""
        from fleetflow_tpu.obs.metrics import REGISTRY
        m = REGISTRY.get("fleet_solver_resident_reuse_total")
        pt = synthetic_problem(60, 8, seed=5)
        sched = TpuSolverScheduler(chains=1, steps=64)
        sched.place(pt)
        before_delta = m.value(outcome="delta")
        before_cold = m.value(outcome="cold")
        cap = pt.capacity.copy()
        cap[0] *= 1.5
        pt2 = dataclasses.replace(pt, capacity=cap)
        r = sched.reschedule(pt2, delta=ProblemDelta(node_valid=pt2.node_valid,
                                                     capacity=cap))
        assert r.feasible
        assert m.value(outcome="delta") == before_delta + 1
        assert m.value(outcome="cold") == before_cold

    def test_env_bucket_flip_mid_life_keeps_staged_contract(self, monkeypatch):
        """The solve's bucket flag must come from the slot's own staging,
        not a fresh env read: flipping FLEET_BUCKET=0 (or retuning the
        tier ladder) after a slot was staged padded must neither skip the
        phantom-row slice (padded-length assignment leaking to the CP)
        nor re-pad the resident prob to a different tier."""
        from fleetflow_tpu.obs.metrics import REGISTRY
        m = REGISTRY.get("fleet_solver_resident_reuse_total")
        pt = synthetic_problem(73, 12, seed=9)   # off-tier: pads to 80
        sched = TpuSolverScheduler(chains=1, steps=64)
        p = sched.place(pt)
        assert p.raw.shape[0] == pt.S
        monkeypatch.setenv("FLEET_BUCKET", "0")
        monkeypatch.setenv("FLEET_BUCKET_MIN", "96")
        before_delta = m.value(outcome="delta")
        valid = pt.node_valid.copy()
        valid[2] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        r = sched.reschedule(pt2, delta=ProblemDelta(node_valid=valid,
                                                     capacity=pt2.capacity))
        assert r.raw.shape[0] == pt.S            # phantom slice still ran
        dead = pt.node_names[2]
        assert not [s for s, n in r.assignment.items() if n == dead]
        assert m.value(outcome="delta") == before_delta + 1

    def test_content_drift_falls_back_cold(self):
        """A relowered stage (fresh arrays, new content) must NOT ride the
        delta path: the bucket-identity gate falls back to cold staging and
        the host-transfer counter records the warm fallback."""
        from fleetflow_tpu.obs.metrics import REGISTRY
        m = REGISTRY.get("fleet_solver_resident_reuse_total")
        hx = REGISTRY.get("fleet_solver_host_transfers_total")
        pt = synthetic_problem(60, 8, seed=6, port_fraction=0.3)
        sched = TpuSolverScheduler(chains=1, steps=64)
        sched.place(pt)
        before_cold = m.value(outcome="cold")
        before_hx = hx.value()
        # content drift the delta contract cannot express: new port ids
        pt2 = dataclasses.replace(pt, port_ids=pt.port_ids.copy())
        r = sched.reschedule(pt2, delta=ProblemDelta(
            node_valid=pt2.node_valid))
        assert r.feasible
        assert m.value(outcome="cold") == before_cold + 1
        assert hx.value() == before_hx + 1

    def test_multi_stage_slots_keep_delta_reuse(self):
        """The CP drives EVERY stage through one scheduler: interleaved
        churn on two same-shape stages must ride each stage's OWN resident
        slot (a single shared slot cold-staged every burst and could
        warm-seed one stage from the other's assignment). Both synthetic
        stages carry IDENTICAL service name lists — only the CP's stage
        key can tell them apart, which is exactly the production shape
        (two stages of one project share service names)."""
        from fleetflow_tpu.obs.metrics import REGISTRY
        m = REGISTRY.get("fleet_solver_resident_reuse_total")
        hx = REGISTRY.get("fleet_solver_host_transfers_total")
        pt_a = synthetic_problem(60, 12, seed=21)
        pt_b = synthetic_problem(60, 12, seed=22)
        assert pt_a.service_names == pt_b.service_names
        sched = TpuSolverScheduler(chains=1, steps=128)
        sched.place(pt_a, stage="demo/staging")
        sched.place(pt_b, stage="demo/prod")
        before_delta = m.value(outcome="delta")
        before_cold = m.value(outcome="cold")
        before_hx = hx.value()
        for burst, node in enumerate((2, 3)):
            for pt, stage in ((pt_a, "demo/staging"), (pt_b, "demo/prod")):
                valid = pt.node_valid.copy()
                valid[node] = False
                pt2 = dataclasses.replace(pt, node_valid=valid)
                r = sched.reschedule(pt2, delta=ProblemDelta(
                    node_valid=valid, capacity=pt2.capacity), stage=stage)
                assert r.feasible
                assert not np.any(np.asarray(r.raw) == node)
                pt.node_valid = valid
        assert m.value(outcome="delta") == before_delta + 4
        assert m.value(outcome="cold") == before_cold
        assert hx.value() == before_hx

    def test_keyed_call_reclaims_keyless_slot(self):
        """A library consumer may mix keyless and keyed calls on one
        scheduler: a later keyed call must adopt the stage's existing
        keyless slot (stamping the key) instead of leaking a second
        device-resident copy of the padded problem."""
        from fleetflow_tpu.obs.metrics import REGISTRY
        hx = REGISTRY.get("fleet_solver_host_transfers_total")
        pt = synthetic_problem(60, 8, seed=13)
        sched = TpuSolverScheduler(chains=1, steps=64)
        sched.place(pt)                       # keyless slot
        assert len(sched._residents) == 1
        before_hx = hx.value()
        # content drift (a relower): delta contract broken -> cold reclaim
        pt2 = dataclasses.replace(pt, port_ids=pt.port_ids.copy())
        r = sched.reschedule(pt2, delta=ProblemDelta(
            node_valid=pt2.node_valid), stage="demo/k")
        assert r.feasible
        assert len(sched._residents) == 1
        assert sched._residents[0].key == "demo/k"
        assert hx.value() == before_hx + 1

    def test_in_place_mutation_synthesizes_delta(self):
        """The CP's node_event mutates pt.node_valid in place; without an
        explicit ProblemDelta the scheduler must detect the drift and merge
        it on device (the round-2 stale-mask bug, now on the resident
        path)."""
        pt = synthetic_problem(60, 8, seed=11)
        sched = TpuSolverScheduler(chains=1, steps=64)
        first = sched.place(pt)
        victims = np.flatnonzero(np.asarray(first.raw) == 0)
        assert victims.size
        pt.node_valid = pt.node_valid.copy()
        pt.node_valid[0] = False
        second = sched.reschedule(pt)
        assert second.feasible
        assert not np.any(np.asarray(second.raw) == 0)


class TestFusedPrerepair:
    def test_fused_prologue_relocates_stranded(self):
        """Direct warm solves (host init) default to the fused prologue:
        no prerepair_ms phase, stranded services still come home."""
        pt = synthetic_problem(100, 10, seed=3)
        res = solve(pt, chains=2, steps=200, seed=3)
        assert res.feasible
        dead = int(np.bincount(res.assignment, minlength=pt.N).argmax())
        valid = pt.node_valid.copy()
        valid[dead] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        res2 = solve(pt2, chains=2, steps=200, seed=4,
                     init_assignment=res.assignment)
        assert res2.feasible
        assert res2.fused_prerepair
        assert "prerepair_ms" not in res2.timings_ms
        assert not (res2.assignment == dead).any()


class TestZeroSweepTrustedStats:
    """ROADMAP item 2 shave: a resident warm dispatch that exits at
    sweeps==0 with a feasible pre-repair trusts the carried stats instead
    of re-running the from-scratch kernels — parity pinned here against
    the recomputed path (device violation_stats + host verify +
    soft_score_host)."""

    def test_trusted_zero_sweep_stats_match_recompute(self):
        from fleetflow_tpu.solver.buckets import (pad_assignment,
                                                  soft_score_host)
        from fleetflow_tpu.solver.kernels import violation_stats

        pt = synthetic_problem(73, 12, seed=3, port_fraction=0.3,
                               volume_fraction=0.2)
        rp = ResidentProblem(pt)
        solve(pt, prob=rp.prob, resident=rp, seed=3, steps=16, bucket=True)
        # capacity-only churn: the standing assignment stays feasible, so
        # the fused prologue lands feasible and the dispatch exits at 0
        # sweeps — the trusted-stats path under test
        cap = pt.capacity.copy()
        cap *= 1.25
        pt2 = dataclasses.replace(pt, capacity=cap)
        rp.apply_delta(pt2, ProblemDelta(capacity=cap))
        res = solve(pt2, prob=rp.prob, resident=rp, resident_warm=True,
                    seed=11, steps=16, bucket=True)
        assert res.steps == 0, \
            "expected the feasible-prologue 0-sweep exit (trusted stats)"
        assert res.violations == 0 and res.pre_repair_violations == 0
        # recomputed paths agree with the trusted zeros:
        # 1. host numpy ground truth on the real rows
        assert verify(pt2, res.assignment)["total"] == 0
        # 2. the device from-scratch kernel on the padded winner (exactly
        #    what the skipped recompute would have produced)
        padded = pad_assignment(res.assignment, rp.prob.S, pt2.node_valid)
        dstats = violation_stats(rp.prob, np.asarray(padded))
        assert float(dstats["total"]) == 0.0
        # 3. the reported soft is the exact host objective of the winner
        assert res.soft == pytest.approx(
            soft_score_host(pt2, res.assignment), abs=1e-6)


class TestResultOwnership:
    """The PR 14 bug class: on the CPU backend `jax.device_get` of the
    resident assignment slot returns a zero-copy VIEW of the device
    buffer, and that slot is donated into the next warm merge dispatch.
    A resident-warm `solve` must hand back a host-owned copy; this test
    holds one bit-identical through later warm dispatches."""

    def test_warm_result_survives_later_warm_dispatches(self):
        rng = np.random.default_rng(17)
        pt = synthetic_problem(73, 12, seed=17, port_fraction=0.3,
                               volume_fraction=0.2)
        rp = ResidentProblem(pt)
        solve(pt, prob=rp.prob, resident=rp, seed=17, steps=16,
              bucket=True)
        pt, delta = _churn_step(pt, rng)
        rp.apply_delta(pt, delta)
        res = solve(pt, prob=rp.prob, resident=rp, resident_warm=True,
                    seed=18, steps=16, bucket=True)
        kept = res.assignment
        # ownership: the result's base must be a host-owned copy, never
        # a wrapper over the resident device slot
        assert kept.base is None or kept.base.flags["OWNDATA"], \
            "solve returned a view of the resident assignment slot"
        pinned = kept.copy()
        for step in range(3):
            pt, delta = _churn_step(pt, rng)
            rp.apply_delta(pt, delta)
            solve(pt, prob=rp.prob, resident=rp, resident_warm=True,
                  seed=19 + step, steps=16, bucket=True)
        assert np.array_equal(kept, pinned), \
            "warm result clobbered in place by a later warm dispatch" \
            " (donated device_get view — the PR 14 aliasing class)"
