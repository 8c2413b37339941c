"""Fleet-horizon store tests (obs/tsdb.py): the fixed-memory ring
semantics, the series selector, windowed aggregates (counter rate,
sketch quantiles), the explicit-interval aggregates the bench legs use,
the deterministic capture digest (the chaos artifact contract), and the
two export formats.

Everything runs on an injected virtual clock — the docstring promise
that a captured scenario's timestamps are exact and replay
byte-identically is pinned here, process-locally, before test_collector
pins it through the chaos runner.
"""

from __future__ import annotations

import json

from fleetflow_tpu.obs.tsdb import (AGGREGATES, SCHEMA_VERSION,
                                    TimeSeriesDB, iter_registry_samples,
                                    snapshot_digest)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def db(**kw) -> tuple[TimeSeriesDB, FakeClock]:
    clock = FakeClock()
    kw.setdefault("clock", clock)
    return TimeSeriesDB(**kw), clock


# --------------------------------------------------------------------------
# ring + cap semantics
# --------------------------------------------------------------------------

class TestRing:
    def test_ring_evicts_oldest_keeps_lifetime_total(self):
        tsdb, clock = db(capacity_per_series=4)
        for i in range(10):
            clock.advance(1.0)
            assert tsdb.record("g", float(i))
        (s,) = tsdb.match("g")
        assert s.total == 10
        assert [v for _t, v in s.samples()] == [6.0, 7.0, 8.0, 9.0]
        # the store's lifetime counter survives eviction too
        assert tsdb.stats()["samples_total"] == 10

    def test_max_series_drops_new_never_evicts_live(self):
        tsdb, clock = db(max_series=2)
        assert tsdb.record("a", 1.0)
        assert tsdb.record("b", 1.0)
        assert not tsdb.record("c", 1.0)       # refused, not evicted
        assert tsdb.stats()["dropped_series"] == 1
        # existing series keep accepting after the cap is hit
        clock.advance(1.0)
        assert tsdb.record("a", 2.0)
        assert len(tsdb) == 2
        assert tsdb.names() == ["a", "b"]

    def test_distinct_labels_are_distinct_series(self):
        tsdb, _ = db()
        tsdb.record("q", 1.0, labels={"tenant": "t1"})
        tsdb.record("q", 2.0, labels={"tenant": "t2"})
        tsdb.record("q", 3.0, labels={"tenant": "t1"})  # same series
        assert len(tsdb) == 2
        (s1,) = tsdb.match("q", labels={"tenant": "t1"})
        assert s1.total == 2

    def test_record_uses_injected_clock_when_t_omitted(self):
        tsdb, clock = db()
        clock.t = 42.5
        tsdb.record("g", 1.0)
        (s,) = tsdb.match("g")
        assert s.last() == (42.5, 1.0)


# --------------------------------------------------------------------------
# selector
# --------------------------------------------------------------------------

class TestMatch:
    def test_labels_match_as_subset(self):
        tsdb, _ = db()
        tsdb.record("m", 1.0, labels={"agent": "n1", "tier": "S"})
        tsdb.record("m", 2.0, labels={"agent": "n2", "tier": "S"})
        tsdb.record("other", 3.0, labels={"agent": "n1"})
        assert len(tsdb.match(labels={"agent": "n1"})) == 2
        assert len(tsdb.match("m", labels={"agent": "n1"})) == 1
        assert len(tsdb.match("m", labels={"tier": "S"})) == 2
        assert tsdb.match("m", labels={"tier": "G"}) == []

    def test_match_order_is_deterministic(self):
        tsdb, _ = db()
        tsdb.record("z", 1.0)
        tsdb.record("a", 1.0, labels={"k": "2"})
        tsdb.record("a", 1.0, labels={"k": "1"})
        got = [(s.name, s.labels) for s in tsdb.match()]
        assert got == sorted(got)


# --------------------------------------------------------------------------
# aggregates
# --------------------------------------------------------------------------

class TestAggregate:
    def test_gauge_aggregate_block(self):
        tsdb, clock = db()
        for v in (3.0, 1.0, 2.0):
            clock.advance(1.0)
            tsdb.record("g", v)
        (row,) = tsdb.aggregate("g")
        agg = row["agg"]
        assert set(AGGREGATES) <= set(agg)
        assert agg["count"] == 3
        assert (agg["min"], agg["max"], agg["last"]) == (1.0, 3.0, 2.0)
        assert agg["mean"] == 2.0
        assert agg["rate"] is None          # gauges have no rate

    def test_counter_rate_is_delta_over_window(self):
        tsdb, clock = db()
        tsdb.record("c", 10.0, t=0.0, kind="counter")
        tsdb.record("c", 30.0, t=4.0, kind="counter")
        (row,) = tsdb.aggregate("c")
        assert row["kind"] == "counter"
        assert row["agg"]["rate"] == 5.0    # (30-10)/(4-0)

    def test_single_sample_counter_has_no_rate(self):
        tsdb, _ = db()
        tsdb.record("c", 10.0, t=0.0, kind="counter")
        (row,) = tsdb.aggregate("c")
        assert row["agg"]["rate"] is None

    def test_window_excludes_old_samples(self):
        tsdb, clock = db()
        tsdb.record("g", 1.0, t=0.0)
        tsdb.record("g", 9.0, t=100.0)
        clock.t = 100.0
        (row,) = tsdb.aggregate("g", window_s=10.0)
        assert row["agg"]["count"] == 1
        assert row["agg"]["last"] == 9.0
        # empty window still yields a row (fleet top filters count==0)
        clock.t = 500.0
        (row,) = tsdb.aggregate("g", window_s=10.0)
        assert row["agg"] == {"count": 0}

    def test_quantiles_ride_the_deterministic_sketch(self):
        tsdb, clock = db(capacity_per_series=256)
        for i in range(100):
            clock.advance(1.0)
            tsdb.record("g", float(i))
        (row,) = tsdb.aggregate("g")
        agg = row["agg"]
        assert agg["p50"] <= agg["p90"] <= agg["p99"] <= agg["max"]
        assert 30.0 <= agg["p50"] <= 70.0

    def test_query_limit_caps_per_series_newest_kept(self):
        tsdb, _ = db()
        for t in range(5):
            tsdb.record("g", float(t), t=float(t))
        (row,) = tsdb.query("g", limit=2)
        assert row["samples"] == [[3.0, 3.0], [4.0, 4.0]]


# --------------------------------------------------------------------------
# capture digest (the chaos artifact contract)
# --------------------------------------------------------------------------

def _fill(tsdb: TimeSeriesDB) -> None:
    for t in range(5):
        tsdb.record("fleet_x", t * 1.5, t=float(t), kind="counter")
        tsdb.record("fleet_y", 10.0 - t, labels={"agent": "n1"},
                    t=float(t))


class TestSnapshot:
    def test_same_content_same_digest(self):
        a, _ = db()
        b, _ = db()
        _fill(a)
        _fill(b)
        sa, sb = a.snapshot(), b.snapshot()
        assert sa["digest"] == sb["digest"]
        assert sa == sb
        assert sa["schema_version"] == SCHEMA_VERSION

    def test_any_divergence_changes_digest(self):
        a, _ = db()
        b, _ = db()
        _fill(a)
        _fill(b)
        b.record("fleet_x", 99.0, t=9.0, kind="counter")
        assert a.snapshot()["digest"] != b.snapshot()["digest"]

    def test_digest_excludes_itself(self):
        tsdb, _ = db()
        _fill(tsdb)
        snap = tsdb.snapshot()
        assert snapshot_digest(snap) == snap["digest"]
        # idempotent: digesting the digested snapshot agrees
        assert snapshot_digest(dict(snap)) == snap["digest"]

    def test_snapshot_is_json_round_trippable(self):
        tsdb, _ = db()
        _fill(tsdb)
        snap = tsdb.snapshot()
        assert json.loads(json.dumps(snap, sort_keys=True)) == snap


# --------------------------------------------------------------------------
# export formats
# --------------------------------------------------------------------------

class TestExport:
    def test_openmetrics_dump(self):
        tsdb, _ = db()
        _fill(tsdb)
        text = tsdb.render_openmetrics()
        assert "# TYPE fleet_x counter" in text
        assert "# TYPE fleet_y gauge" in text
        assert 'fleet_y{agent="n1"} 10 0.000000' in text
        assert text.endswith("# EOF\n")
        # one TYPE line per family, not per series
        assert text.count("# TYPE fleet_x") == 1

    def test_jsonl_dump_one_series_per_line(self):
        tsdb, _ = db()
        _fill(tsdb)
        rows = [json.loads(ln) for ln in
                tsdb.export_jsonl().splitlines()]
        assert len(rows) == 2
        by_name = {r["name"]: r for r in rows}
        assert by_name["fleet_x"]["kind"] == "counter"
        assert by_name["fleet_y"]["labels"] == {"agent": "n1"}
        assert len(by_name["fleet_x"]["samples"]) == 5


# --------------------------------------------------------------------------
# registry flattening
# --------------------------------------------------------------------------

class TestIterRegistrySamples:
    def test_counter_gauge_histogram_flatten(self):
        snap = {
            "c": {"type": "counter",
                  "values": [{"labels": {"k": "v"}, "value": 3}]},
            "g": {"type": "gauge", "values": [{"labels": {}, "value": 7}]},
            "h": {"type": "histogram",
                  "values": [{"labels": {}, "sum": 1.5, "count": 4}]},
        }
        got = sorted(iter_registry_samples(snap))
        assert got == [("c", {"k": "v"}, 3.0, "counter"),
                       ("g", {}, 7.0, "gauge"),
                       ("h_count", {}, 4.0, "counter"),
                       ("h_sum", {}, 1.5, "counter")]
