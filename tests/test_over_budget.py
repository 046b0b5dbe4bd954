"""A store larger than the device window's budget: what eviction leaves
after live ingest and after a restart, the counters that say so, every
answer on either side of the horizon against the float64 oracle, the
raw plan's spans, and the flag that sets the budget."""

import json

import numpy as np
import pytest

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.ops import oracle
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.tools import cli
from opentsdb_tpu.utils.config import Config
from tests.test_resident_tracing import q, serve, stat

# The deployment tsbs-cpu4k-13h at a size the CPU holds: 13 h of 10 s
# data from half past an hour, a budget of 0.358 of what is stored, and
# 64 chunks to the budget (tools/cli.py derives the same).
T0 = 1356998400 + 1800
STEP = 10
STEPS = 13 * 360
HOSTS = 12
METRICS = [f"ob.m{i}" for i in range(5)]
STORED = len(METRICS) * HOSTS * STEPS
BUDGET = int(0.358 * STORED)
STAGING = BUDGET // 64
SLICE = 30                      # steps a live slice: five minutes
END = T0 + STEP * (STEPS - 1)
EXACT = {"max", "min", "count"}
RTOL = 1e-4                     # the config's f32_rtol
AGGREGATE = ["aggregate.pack", "aggregate.dispatch", "aggregate.wait",
             "aggregate.fetch", "aggregate.results"]


def open_tsdb(wal_dir, **over) -> TSDB:
    kw = dict(auto_create_metrics=True, port=0, bind="127.0.0.1",
              backend="tpu", device_window=True, enable_rollups=False,
              wal_path=str(wal_dir), device_window_points=BUDGET,
              device_window_staging=STAGING)
    kw.update(over)
    return TSDB(MemKVStore(wal_path=str(wal_dir / "wal")), Config(**kw),
                start_compaction_thread=False)


def walk_values() -> dict:
    """(metric, host) -> float32 values: clamped random walks of two
    decimals, as the benchmark's generator makes them."""
    rng = np.random.default_rng(13)
    out = {}
    for m in METRICS:
        for h in range(HOSTS):
            v = 50 + np.cumsum(rng.normal(0, 1, STEPS))
            out[m, h] = np.round(np.clip(v, 0, 100), 2).astype(np.float32)
    return out


def window_state(tsdb) -> dict:
    """Per metric name: (complete_from, resident points, chunk extents)
    after everything staged is on the device."""
    dw = tsdb.devwindow
    dw.flush()
    out = {}
    for m in METRICS:
        mw = dw._metrics[tsdb.metrics.get_id(m)]
        out[m] = (mw.complete_from, mw.device_points,
                  [c["max_ts"] - c["min_ts"] for c in mw.chunks])
    return out


def counters(dw) -> dict:
    return {"appended": dw.appended_points, "evicted": dw.evicted_points,
            "resident": sum(mw.device_points
                            for mw in dw._metrics.values()),
            "total": dw._total_points}


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """The store after live time-major ingest, shut down; with what its
    window held just before."""
    wal_dir = tmp_path_factory.mktemp("over_budget")
    values = walk_values()
    tsdb = open_tsdb(wal_dir)
    ts = T0 + STEP * np.arange(STEPS, dtype=np.int64)
    for lo in range(0, STEPS, SLICE):
        for m in METRICS:
            for h in range(HOSTS):
                tsdb.add_batch(m, ts[lo:lo + SLICE],
                               values[m, h][lo:lo + SLICE],
                               {"host": f"h{h}"})
    live = {"state": window_state(tsdb), "counters": counters(tsdb.devwindow),
            "horizons": tsdb.devwindow.horizons()}
    tsdb.shutdown()
    return wal_dir, values, live


@pytest.fixture(scope="module")
def reopened(store):
    """The same store after a restart: the boot refill alone."""
    wal_dir, values, _live = store
    tsdb = open_tsdb(wal_dir)
    at_boot = counters(tsdb.devwindow)
    state = window_state(tsdb)
    yield tsdb, values, state, at_boot
    tsdb.shutdown()


def assert_newest_of_every_metric(state: dict) -> None:
    froms = [cf for cf, _n, _ext in state.values()]
    assert all(cf is not None for cf in froms), "something must be evicted"
    # Horizons within one chunk of each other: no further apart than the
    # stretch of time the longest resident chunk covers.
    extent = max(e for _cf, _n, ext in state.values() for e in ext)
    assert max(froms) - min(froms) <= extent + STEP, state
    # No metric left a stub: each holds about its share of the budget.
    share = BUDGET / len(METRICS)
    for m, (cf, n, ext) in state.items():
        assert n >= 0.6 * share, (m, n, share)
        assert cf < END - 0.2 * (END - T0), (m, cf)


def test_live_ingest_keeps_the_newest_of_every_metric(store):
    _dir, _values, live = store
    assert_newest_of_every_metric(live["state"])
    lo, hi = live["horizons"]
    assert lo == min(cf for cf, _n, _e in live["state"].values())
    assert hi == max(cf for cf, _n, _e in live["state"].values())
    c = live["counters"]
    assert c["appended"] == STORED
    assert c["appended"] - c["evicted"] == c["resident"] == c["total"]
    assert c["resident"] <= BUDGET


def test_restart_keeps_the_newest_of_every_metric(reopened):
    _tsdb, _values, state, _boot = reopened
    assert_newest_of_every_metric(state)


def test_refill_appends_every_stored_point(reopened):
    tsdb, _values, _state, at_boot = reopened
    # Straight after boot, before anything flushed the staged tails.
    assert at_boot["appended"] == STORED
    assert at_boot["evicted"] > 0
    c = counters(tsdb.devwindow)
    assert c["appended"] == STORED
    assert c["appended"] - c["evicted"] == c["resident"] == c["total"]
    assert c["resident"] <= BUDGET
    assert 0.55 <= c["evicted"] / c["appended"] <= 0.70


def test_a_store_under_its_budget_evicts_nothing(tmp_path):
    tsdb = open_tsdb(tmp_path, device_window_points=STORED)
    ts = T0 + STEP * np.arange(360, dtype=np.int64)
    for m in METRICS:
        tsdb.add_batch(m, ts, np.ones(360, np.float32), {"host": "h0"})
    tsdb.devwindow.flush()
    assert tsdb.devwindow.evicted_points == 0
    assert tsdb.devwindow.horizons() == (0, 0)
    tsdb.shutdown()
    again = open_tsdb(tmp_path, device_window_points=STORED)
    again.devwindow.flush()
    assert counters(again.devwindow) == {
        "appended": 360 * len(METRICS), "evicted": 0,
        "resident": 360 * len(METRICS), "total": 360 * len(METRICS)}
    assert again.devwindow.horizons() == (0, 0)
    again.shutdown()


HOST_SETS = {"one": ("h3", [3]), "eight": ("|".join(
    f"h{h}" for h in range(8)), list(range(8))),
    "every": ("*", list(range(HOSTS)))}


def expected(values, metric, hosts, agg, interval, start, end) -> dict:
    """host -> (timestamps, values) by the float64 oracle: a group a
    host, as host=a|b and host=* give."""
    ts = T0 + STEP * np.arange(STEPS, dtype=np.int64)
    m = (ts >= start) & (ts <= end)
    interp = "lerp" if Aggregators.get(agg).interpolates else "none"
    out = {}
    for h in hosts:
        dts, dv = oracle.downsample(ts[m], values[metric, h][m], interval,
                                    agg, mode="aligned", bucket_ts="start")
        out[f"h{h}"] = oracle.group_aggregate([(dts, dv)], agg,
                                              interp=interp)
    return out


@pytest.mark.parametrize("interval", [300, 3600], ids=["5m", "1h"])
@pytest.mark.parametrize("agg", ["max", "min", "count", "avg", "sum"])
def test_answers_on_both_sides_of_the_horizon(reopened, agg, interval):
    tsdb, values, state, _boot = reopened
    dw = tsdb.devwindow
    ex = QueryExecutor(tsdb, backend="tpu")
    metric = METRICS[1]
    horizon = state[metric][0]
    # Starts that are no bucket's start and no point's time.
    ranges = {"resident": (horizon + 7, END, "resident"),
              "straddling": (horizon - 7200 + 7, horizon + 3600, "raw"),
              "evicted": (T0 + 7, horizon - 600, "raw")}
    for where, (start, end, plan) in ranges.items():
        for name, (tagv, hosts) in HOST_SETS.items():
            spec = QuerySpec(metric, {"host": tagv}, agg,
                             downsample=(interval, agg))
            before = dw.horizon_misses, dw.window_hits
            got, served, _cached = ex.run_with_plan(spec, start, end)
            assert served == plan, (where, name)
            assert (dw.horizon_misses - before[0],
                    dw.window_hits - before[1]) == (
                        (1, 0) if plan == "raw" else (0, 1))
            want = expected(values, metric, hosts, agg, interval, start,
                            end)
            assert sorted(r.tags["host"] for r in got) == sorted(want)
            for r in got:
                wts, wv = want[r.tags["host"]]
                np.testing.assert_array_equal(r.timestamps, wts)
                if agg in EXACT:
                    np.testing.assert_array_equal(
                        r.values, wv, err_msg=f"{where} {name}")
                else:
                    np.testing.assert_allclose(
                        r.values, wv, rtol=RTOL,
                        err_msg=f"{where} {name}")


def test_raw_spans_tile_the_request_and_say_what_was_read(reopened):
    tsdb, _values, state, _boot = reopened
    metric = METRICS[2]
    end = state[metric][0] - 600
    m = f"avg:1h-avg:{metric}" + "{host=*}"
    rows0, points0 = stat("query.raw.rows"), stat("query.raw.points")
    # Cold (the program compiles inside the dispatch), then ranges a
    # second on each: one point a series fewer, the same program.
    starts = tuple(range(T0, T0 + 5))
    got = serve(tsdb, *(q(start, end, m) for start in starts))
    read = []
    tiled_top, tiled_agg = [], []
    for start, (st, body) in zip(starts, got):
        assert st == 200
        out = json.loads(body)
        assert len(out) == HOSTS
        assert all(r["rollup"] == "raw" for r in out)
        tree = out[0]["trace"]
        # The two hops between the event loop and the pool lie outside
        # the root's own interval, first and last among its children.
        assert [tree["spans"][i]["name"] for i in (0, -1)] == [
            "http.q.queue", "http.q.resume"]
        top = {s["name"]: s for s in tree["spans"][1:-1]}
        assert list(top) == ["planner.pick", "scan", "aggregate"]
        assert top["planner.pick"]["tags"] == {"plan": "raw",
                                               "miss": "horizon"}
        # The three tile the sub-query, the children tile aggregate:
        # never more than their parent (a rounding a span); what they
        # leave of it is held in the median request, below.
        tops = sum(s["ms"] for s in top.values())
        assert tops <= tree["ms"] + 1e-3 * len(top)
        tiled_top.append((tree["ms"] - tops, tree["ms"]))
        agg = top["aggregate"]
        assert [s["name"] for s in agg["spans"]] == AGGREGATE + [
            "aggregate.results"]
        tiled = sum(s["ms"] for s in agg["spans"])
        assert tiled <= agg["ms"] + 1e-3 * len(agg["spans"])
        tiled_agg.append((agg["ms"] - tiled, agg["ms"]))
        pack = agg["spans"][0]["tags"]
        scan = top["scan"]["tags"]
        assert scan["points"] == HOSTS * len(range(
            start + -start % STEP, end + 1, STEP))
        assert pack["series"] == HOSTS and pack["slots"] >= scan["points"]
        # A fragment the cache held decodes no row.
        assert scan["rows"] % HOSTS == 0
        assert (scan["rows"] == 0) == scan["cached"]
        kids = top["scan"]["spans"]
        assert kids[-1]["name"] == "scan.group"
        assert kids[-1]["tags"] == {"series": HOSTS, "groups": HOSTS}
        assert {s["name"] for s in kids[:-1]} <= {"chunk.decode"}
        read.append((scan["rows"], scan["points"], pack["slots"]))
    assert read[0][0] > 0
    # One padded length, so one program for both.
    assert read[0][2] == read[1][2] and read[0][1] != read[1][1]
    assert stat("query.raw.points") - points0 == sum(r[1] for r in read)
    assert stat("query.raw.rows") - rows0 == sum(r[0] for r in read)
    # (On twelve series a warm aggregate is a millisecond or two; what
    # the children leave is a fixed fraction of one.) The median
    # request's: where the host takes the core away between two spans
    # of one request is its to say.
    for tiled in (tiled_top, tiled_agg):
        left, of = sorted(tiled)[len(tiled) // 2]
        assert left <= max(0.01 * of, 0.5), tiled


def test_the_flag_reaches_the_window_and_stats(tmp_path, monkeypatch):
    seen = []
    monkeypatch.setattr(cli, "cmd_tsd", lambda args: seen.append(args) or 0)

    def config_of(*flags):
        assert cli.main(["tsd", "--port", "0", "--wal",
                         str(tmp_path / "wal"), "--auto-metric",
                         *flags]) == 0
        tsdb = cli.make_tsdb(seen[-1])
        return tsdb

    tsdb = config_of()
    try:
        assert tsdb.config.device_window_points == 1 << 26
        assert tsdb.config.device_window_staging == 1 << 20
    finally:
        tsdb.shutdown()
    tsdb = config_of("--device-window-points", str(1 << 30))
    try:        # never a larger upload than the default's
        assert tsdb.config.device_window_staging == 1 << 20
    finally:
        tsdb.shutdown()
    with pytest.raises(SystemExit):
        config_of("--device-window-points", "-1")
    tsdb = config_of("--device-window-points", "670000")
    try:
        assert tsdb.devwindow.max_points == 670000
        assert tsdb.devwindow.staging_points == 670000 // 64
        (st, body), = serve(tsdb, "/stats")
        assert st == 200
        lines = {ln.split()[0]: ln.split()[2]
                 for ln in body.decode().splitlines()}
        assert lines["tsd.devwindow.points.budget"] == "670000"
        assert lines["tsd.devwindow.misses.horizon"] == "0"
        assert lines["tsd.devwindow.horizon.min"] == "0"
        assert lines["tsd.devwindow.horizon.max"] == "0"
    finally:
        tsdb.shutdown()


def test_scan_series_sorts_a_series_whose_cells_come_out_of_time_order(
        tmp_path):
    """A row-hour written as one compacted cell and then given a late
    point holds two cells, and the second lies before the first one's
    end: the one-key stable sort of scan_series is not enough there and
    it must fall back to sorting by (series, timestamp)."""
    tsdb = TSDB(MemKVStore(wal_path=str(tmp_path / "wal")),
                Config(auto_create_metrics=True, backend="cpu",
                       wal_path=str(tmp_path)),
                start_compaction_thread=False)
    try:
        ts = T0 + STEP * np.arange(180, dtype=np.int64)
        for h in range(3):
            tsdb.add_batch("ob.late", ts, np.arange(180, dtype=np.float32),
                           {"host": f"h{h}"})
        tsdb.add_point("ob.late", int(T0 + 5), 7.5, {"host": "h1"})
        counts = {}
        _keys, per_series = tsdb.scan_series(b"", b"\xff" * 64,
                                             counts=counts)
        assert counts["rows"] == 3
        assert len(per_series) == 3
        for skey, cols in per_series.items():
            late = len(cols.timestamps) == 181
            want = np.sort(np.append(ts, T0 + 5)) if late else ts
            np.testing.assert_array_equal(cols.timestamps, want)
            assert (cols.values[1] == 7.5) == late
        assert sum(len(c.timestamps) for c in per_series.values()) == 541
    finally:
        tsdb.shutdown()
