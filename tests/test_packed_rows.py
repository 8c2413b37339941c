"""A row of the bit-packed eligibility plane is unpacked from its words.

`solver/problem.py` reads the plane two ways: a point `(s, node)` gathers
its one word (`eligible_lookup`), a whole row gathers its W words and
unpacks all 32 bits of each (`eligible_row`, `eligible_rows`). The second
must be the dense plane's row bit for bit, and the seeds that call it must
never look a row up cell by cell: one gather a (row, node) cell cost the
batched seed 118 ms of a 2,000 x 5,000 solve on a v5e (PERF.md section 6,
PR 40), and a CPU times both forms alike, so only the traced program can
hold the rule here.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.solver import greedy
from fleetflow_tpu.solver.greedy import (greedy_place, greedy_place_batched,
                                         placement_order)
from fleetflow_tpu.solver.problem import (eligible_lookup, eligible_row,
                                          eligible_rows, pack_bool_rows,
                                          packed_width, prepare_problem)

REAL_ROWS = 37


def _planes(N: int, seed: int):
    """A dense plane and its packing, the last three rows phantoms as
    `buckets` stages them: all-ones words, pad bits of the last word set."""
    rng = np.random.default_rng(seed)
    real = rng.random((REAL_ROWS, N)) < 0.6
    dense = np.concatenate([real, np.ones((3, N), bool)])
    packed = np.concatenate(
        [pack_bool_rows(real),
         np.full((3, packed_width(N)), 0xFFFFFFFF, np.uint32)])
    return dense, packed


@pytest.mark.parametrize("M", [1, 256])
@pytest.mark.parametrize("N", [1, 31, 32, 33, 157, 5000])
def test_a_packed_row_unpacks_to_the_dense_row(N, M):
    dense, packed = _planes(N, seed=N)
    rng = np.random.default_rng(N + M)
    # with replacement: rows repeat once M passes the plane's 40, and the
    # phantoms are always among them
    svc = rng.integers(0, dense.shape[0], size=M)
    svc[-1] = dense.shape[0] - 1
    plane, rows = jnp.asarray(packed), jnp.asarray(svc, jnp.int32)

    got = jax.jit(eligible_rows, static_argnums=2)(plane, rows, N)
    assert got.dtype == jnp.bool_ and got.shape == (M, N)
    np.testing.assert_array_equal(np.asarray(got), dense[svc])
    # the dense layout's branch, and the point lookup the parent read a
    # row by: one answer
    np.testing.assert_array_equal(
        np.asarray(eligible_rows(jnp.asarray(dense), rows, N)), dense[svc])
    cols = jnp.arange(N, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(eligible_lookup(plane, rows[:, None], cols[None, :])),
        dense[svc])

    one = jax.jit(eligible_row, static_argnums=2)
    for s in {int(svc[0]), int(svc[-1])}:
        row = one(plane, jnp.int32(s), N)
        assert row.dtype == jnp.bool_ and row.shape == (N,)
        np.testing.assert_array_equal(np.asarray(row), dense[s])
        np.testing.assert_array_equal(
            np.asarray(eligible_row(jnp.asarray(dense), s, N)), dense[s])


def _problem(spread: bool):
    pt = synthetic_problem(400, 1000, seed=11, n_tenants=4)
    assert not pt.eligible.all()
    if spread:
        pt = dataclasses.replace(
            pt, node_topology=(np.arange(pt.N) % 3).astype(np.int32),
            max_skew=1)
    order = jnp.asarray(placement_order(pt.demand, pt.dep_depth))
    return pt, order


@pytest.mark.parametrize("spread", [True, False], ids=["spread", "plain"])
def test_the_batched_seed_places_alike_from_a_packed_and_a_dense_plane(
        spread):
    pt, order = _problem(spread)
    packed = prepare_problem(pt, packed=True)
    dense = prepare_problem(pt, packed=False)
    assert packed.eligible.dtype == jnp.uint32
    assert dense.eligible.dtype == jnp.bool_
    a = np.asarray(greedy_place_batched(packed, order, batch=128))
    b = np.asarray(greedy_place_batched(dense, order, batch=128))
    np.testing.assert_array_equal(a, b)
    assert pt.eligible[np.arange(pt.S), a].all()


# --------------------------------------------------------------------------
# the structure of the traced seeds
# --------------------------------------------------------------------------

def _gathers(jaxpr):
    """(operand dtype, lookups) of every `gather` equation, sub-jaxprs
    included. A lookup is one index row: `used[:, safe]` makes M x K of
    them for N-long columns, a per-cell read makes one an element."""
    for eq in jaxpr.eqns:
        if eq.primitive.name == "gather":
            operand, indices = eq.invars[:2]
            yield (operand.aval.dtype,
                   math.prod(indices.aval.shape[:-1]))
        for v in eq.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    yield from _gathers(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _gathers(sub)


def _per_cell_rows(eligible, svc, N):
    cols = jnp.arange(N, dtype=jnp.int32)
    return eligible_lookup(eligible, svc[:, None], cols[None, :])


def _per_cell_row(eligible, s, N):
    return eligible_lookup(eligible, s, jnp.arange(N, dtype=jnp.int32))


@pytest.mark.parametrize("spread", [True, False], ids=["spread", "plain"])
def test_the_batched_seed_traces_no_gather_of_a_lookup_a_cell(
        spread, monkeypatch):
    """The issue words the guard by a gather's output size; `used[:, safe]`
    (the slice form it names as cheap) has an (N, M, K) output, so the
    guard counts lookups: index rows, whatever each fetches."""
    pt, order = _problem(spread)
    prob = prepare_problem(pt, packed=True)
    M, N = 128, pt.N

    def trace():
        return jax.make_jaxpr(
            lambda p, o: greedy_place_batched.__wrapped__(p, o, batch=M))(
                prob, order)

    found = list(_gathers(trace().jaxpr))
    assert found
    assert max(n for _, n in found) < M * N
    # the plane is read by rows: M lookups of W words
    plane = [n for dt, n in found if dt == jnp.uint32]
    assert plane and max(plane) == M
    # what the guard is for: the parent's read is a lookup a cell
    monkeypatch.setattr(greedy, "eligible_rows", _per_cell_rows)
    assert max(n for dt, n in _gathers(trace().jaxpr)
               if dt == jnp.uint32) == M * N


@pytest.mark.parametrize("spread", [True, False], ids=["spread", "plain"])
def test_the_scan_seed_reads_its_row_of_the_plane_by_words(
        spread, monkeypatch):
    pt, order = _problem(spread)
    prob = prepare_problem(pt, packed=True)

    def plane_lookups():
        jaxpr = jax.make_jaxpr(
            lambda p, o: greedy_place.__wrapped__(p, o))(prob, order)
        return [n for dt, n in _gathers(jaxpr.jaxpr) if dt == jnp.uint32]

    # one row a step: a slice of W words, or a gather of one lookup
    assert all(n < pt.N for n in plane_lookups())
    monkeypatch.setattr(greedy, "eligible_row", _per_cell_row)
    assert max(plane_lookups()) == pt.N
