"""Shape bucketing (solver/buckets.py): tier ladder, phantom inertness,
bucketed-vs-exact solve parity, and the executable-reuse contract.

The reuse test is the CI tier-1 acceptance for the warm path: two fleet
sizes inside one bucket must share ONE compiled `_refine` executable
(`_refine._cache_size()` telemetry, the same counter solve() reports as
`compiles`). The parity sweep is the hypothesis-style property the PR
promises: for random problems, a bucketed solve reports the same
violations as an exact-shape solve and never leaks a phantom row.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.solver import (bucket_config, prepare_problem, solve,
                                  soft_score_host)
from fleetflow_tpu.solver.api import _refine
from fleetflow_tpu.solver.buckets import (BucketConfig, bucket_bounds,
                                          bucket_size, pad_assignment,
                                          pad_problem, pad_problem_tiers,
                                          width_bucket)
from fleetflow_tpu.solver.problem import pack_bool_rows
from fleetflow_tpu.solver.repair import verify


def _drop_rows(pt, keep: int):
    """The churn shape: the same fleet config minus its last rows."""
    return dataclasses.replace(
        pt,
        demand=pt.demand[:keep], dep_adj=pt.dep_adj[:keep, :keep],
        dep_depth=pt.dep_depth[:keep], port_ids=pt.port_ids[:keep],
        volume_ids=pt.volume_ids[:keep], anti_ids=pt.anti_ids[:keep],
        coloc_ids=pt.coloc_ids[:keep], eligible=pt.eligible[:keep],
        service_names=pt.service_names[:keep],
        replica_of=pt.replica_of[:keep],
        preferred=None if pt.preferred is None else pt.preferred[:keep])


class TestLadder:
    def test_bucket_size_covers_and_is_idempotent(self):
        for n in (1, 7, 63, 64, 65, 100, 997, 9997, 10_050, 123_456):
            b = bucket_size(n)
            assert b >= n
            assert bucket_size(b) == b, "a tier must map to itself"

    def test_bucket_size_monotone(self):
        vals = [bucket_size(n) for n in range(1, 2000)]
        assert vals == sorted(vals)

    def test_width_bucket(self):
        assert width_bucket(0) == 4 and width_bucket(1) == 4
        assert width_bucket(4) == 4 and width_bucket(5) == 8

    def test_bucket_bounds_straddle(self):
        lower, upper = bucket_bounds(66)
        assert lower == 64 and upper > 66

    def test_drift_within_tier_shares_bucket(self):
        # the motivating scenario: 9,997 -> 10,050 services, one executable
        assert bucket_size(9_997) == bucket_size(10_050)


class TestPadding:
    def test_phantom_rows_are_inert_by_construction(self):
        pt = synthetic_problem(37, 8, seed=1, port_fraction=0.4)
        prob = prepare_problem(pt)
        padded, info = pad_problem_tiers(prob)
        assert padded.S == info.padded_S > pt.S == info.orig_S
        demand = np.asarray(padded.demand)
        ids = np.asarray(padded.conflict_ids)
        elig = np.asarray(padded.eligible)
        assert (demand[pt.S:] == 0).all()
        assert (ids[pt.S:] == -1).all()
        # packed layout: phantom rows are all-ones words (eligible
        # everywhere) and the preference plane is absent by design
        assert elig.dtype == np.uint32
        assert (elig[pt.S:] == 0xFFFFFFFF).all()
        assert padded.preferred is None
        # real rows byte-identical
        assert np.array_equal(demand[: pt.S], pt.demand)
        assert np.array_equal(elig[: pt.S], pack_bool_rows(pt.eligible))

    def test_pad_problem_tiers_idempotent(self):
        pt = synthetic_problem(37, 8, seed=1)
        padded, _ = pad_problem_tiers(prepare_problem(pt))
        again, info = pad_problem_tiers(padded)
        assert again is padded, "a tiered problem must pass through"
        assert info.pad_waste == 0.0

    def test_pad_problem_multiple_unchanged_contract(self):
        # the sharded entry point: pad S to a device-count multiple
        pt = synthetic_problem(21, 6, seed=2)
        padded, orig = pad_problem(prepare_problem(pt), 8)
        assert orig == 21 and padded.S == 24
        same, orig2 = pad_problem(padded, 8)
        assert same is padded and orig2 == 24

    def test_pad_assignment_uses_valid_fill(self):
        valid = np.array([False, False, True, True])
        out = pad_assignment(np.array([3, 2], dtype=np.int32), 5, valid)
        assert out.shape == (5,)
        assert (out[2:] == 2).all(), "phantoms must park on a VALID node"


class TestSolveParity:
    """The property the PR promises: over ≥20 random seeds, a bucketed
    solve and an exact-shape solve report identical violations, the
    bucketed soft score is exact for the real rows, and no phantom ever
    appears in the returned placement. One fixed shape keeps the sweep to
    two XLA compiles total (tier-1 budget)."""

    SEEDS = range(20)

    @pytest.mark.parametrize("seed", SEEDS)
    def test_bucketed_matches_exact(self, seed):
        pt = synthetic_problem(73, 12, seed=seed, port_fraction=0.3,
                               volume_fraction=0.2)
        exact = solve(pt, seed=seed, steps=16)
        bucketed = solve(pt, seed=seed, steps=16, bucket=True)
        assert bucketed.bucket is not None
        assert bucketed.bucket["padded_S"] > pt.S
        # identical violation verdicts, cross-checked by the numpy oracle
        assert exact.violations == bucketed.violations == 0
        assert verify(pt, bucketed.assignment)["total"] == 0
        # no phantom leaks: exactly S real rows, all on real valid nodes
        assert bucketed.assignment.shape == (pt.S,)
        assert (bucketed.assignment >= 0).all()
        assert (bucketed.assignment < pt.N).all()
        assert pt.node_valid[bucketed.assignment].all()
        # the reported soft is the REAL rows' exact score...
        assert bucketed.soft == pytest.approx(
            soft_score_host(pt, bucketed.assignment), abs=1e-4)
        # ...and lands in the same quality regime as the exact solve
        assert bucketed.soft == pytest.approx(exact.soft, abs=0.25)


class TestExecutableReuse:
    """CI acceptance: a second fleet size inside the same bucket triggers
    ZERO new XLA compiles of the fused pipeline."""

    def test_same_bucket_zero_recompile(self):
        pt = synthetic_problem(117, 16, seed=3, port_fraction=0.3,
                               volume_fraction=0.2)
        first = solve(pt, seed=5, bucket=True)
        assert first.violations == 0
        cache_before = _refine._cache_size()
        pt2 = _drop_rows(pt, 109)     # drifted fleet, same bucket
        second = solve(pt2, seed=6, bucket=True)
        assert second.violations == 0
        assert second.bucket["padded_S"] == first.bucket["padded_S"]
        assert _refine._cache_size() == cache_before, \
            "same-bucket solve recompiled the fused pipeline"
        assert second.bucket["hit"] is True

    def test_second_size_restage_rides_arena_fast_path(self):
        """The pipeline bench's second-size restage (ISSUE 14 satellite):
        staging a drifted fleet size in the same tier through
        `stage_problem_tiers` must be compile-free (pure memcpy +
        device_put) and reuse the per-tier host arenas — r08 regressed
        this leg 6.4 -> 62.1 ms by routing through prepare_problem +
        on-device pad_problem_tiers (eager jnp.pad per plane)."""
        import jax

        from fleetflow_tpu.solver import (stage_problem_tiers,
                                          staging_arena_stats)

        pt = synthetic_problem(117, 16, seed=11, port_fraction=0.3,
                               volume_fraction=0.2)
        cfg = bucket_config()
        prob1, info1 = stage_problem_tiers(pt, cfg)
        jax.block_until_ready(prob1)
        arenas_before = staging_arena_stats()
        pt2 = _drop_rows(pt, 109)     # drifted fleet, same tier
        old_log, watched = jax.config.jax_log_compiles, []
        import logging

        class _H(logging.Handler):
            def emit(self, rec):
                if "Compiling" in rec.getMessage():
                    watched.append(rec.getMessage())

        h = _H()
        logging.getLogger("jax._src.interpreters.pxla").addHandler(h)
        jax.config.update("jax_log_compiles", True)
        try:
            prob2, info2 = stage_problem_tiers(pt2, cfg)
            jax.block_until_ready(prob2)
        finally:
            jax.config.update("jax_log_compiles", old_log)
            logging.getLogger("jax._src.interpreters.pxla").removeHandler(h)
        assert info2.padded_S == info1.padded_S
        assert watched == [], f"arena restage compiled XLA: {watched}"
        arenas_after = staging_arena_stats()
        assert arenas_after["arenas"] == arenas_before["arenas"], \
            "same-tier restage allocated new arenas"
        assert arenas_after["arena_bytes"] == arenas_before["arena_bytes"]
        # the restaged tensors are the real thing: same padded shape and
        # a solvable problem
        res = solve(pt2, prob=prob2, bucket=True, seed=12)
        assert res.violations == 0

    def test_warm_reschedule_in_bucket(self):
        pt = synthetic_problem(97, 16, seed=9, port_fraction=0.2)
        base = solve(pt, seed=1, bucket=True)
        assert base.violations == 0
        victim = int(np.bincount(base.assignment,
                                 minlength=pt.N).argmax())
        valid = pt.node_valid.copy()
        valid[victim] = False
        pt2 = dataclasses.replace(pt, node_valid=valid)
        res = solve(pt2, seed=2, bucket=True,
                    init_assignment=base.assignment)
        assert res.violations == 0
        assert res.assignment.shape == (pt.S,)
        assert valid[res.assignment].all()
        # migration stickiness must survive bucketing: only churn-forced
        # moves (plus anneal polish) — never a full reshuffle
        moved = int((res.assignment != base.assignment).sum())
        affected = int((base.assignment == victim).sum())
        assert moved <= affected + pt.S // 4


class TestConfig:
    def test_env_disable(self, monkeypatch):
        monkeypatch.setenv("FLEET_BUCKET", "0")
        assert bucket_config().enabled is False
        pt = synthetic_problem(37, 8, seed=0)
        res = solve(pt, seed=0, bucket=True)
        assert res.bucket is None, "FLEET_BUCKET=0 must force-disable"

    def test_skew_buckets_with_real_row_mask(self):
        """Spread constraints used to bypass bucketing (phantoms would
        count into per-domain totals); padded problems now carry a traced
        n_real and the kernels mask phantom rows out of topology/skew —
        so the CP churn path gets bucket (and resident) reuse at skew
        too, with skew accounting identical to the exact-shape solve."""
        pt = synthetic_problem(37, 8, seed=0)
        pt = dataclasses.replace(pt, max_skew=2)
        res = solve(pt, seed=0, bucket=True)
        assert res.bucket is not None and res.bucket["padded_S"] > pt.S
        exact = solve(pt, seed=0)
        assert res.violations == exact.violations == 0
        # numpy oracle on the REAL rows agrees with the device verdict
        assert verify(pt, res.assignment)["total"] == 0
        assert res.assignment.shape == (pt.S,)

    def test_config_defaults(self):
        cfg = bucket_config()
        assert isinstance(cfg, BucketConfig)
        assert cfg.enabled and cfg.minimum >= 8
