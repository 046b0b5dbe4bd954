"""Device-resident hot window (storage/devstore.py + executor path).

The window must be invisible semantically: every query it serves must be
byte-identical (grids) / float32-identical (values) to the storage scan
path, and anything it cannot guarantee (out-of-order writes, evicted
ranges, un-downsampled queries) must fall back rather than approximate.
One explicit opt-in exception: Config.wire_bf16 trades value precision
(bfloat16 on the wire) for fetch payload — tested to tolerance below.
"""

import numpy as np
import pytest

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.storage.devstore import DeviceWindow
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.utils.config import Config

BT = 1356998400


@pytest.fixture
def tsdb():
    t = TSDB(MemKVStore(), Config(auto_create_metrics=True,
                                  enable_sketches=False),
             start_compaction_thread=False)
    yield t
    t.compactionq.shutdown()


def _load(tsdb, series=12, points=200, span=7200, metric="m.cpu"):
    rng = np.random.default_rng(7)
    for i in range(series):
        ts = BT + np.sort(rng.choice(span, points, replace=False))
        tsdb.add_batch(metric, ts, rng.normal(100, 10, points),
                       {"host": f"h{i}", "dc": "east" if i % 2 else "west"})


def _compare(tsdb, spec, start=BT, end=BT + 7200, expect_hit=True):
    ex = QueryExecutor(tsdb, backend="tpu")
    h0 = tsdb.devwindow.window_hits
    got = ex.run(spec, start, end)
    hit = tsdb.devwindow.window_hits > h0
    assert hit == expect_hit, f"window hit={hit}, wanted {expect_hit}"
    dw, tsdb.devwindow = tsdb.devwindow, None
    try:
        want = ex.run(spec, start, end)
    finally:
        tsdb.devwindow = dw
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.tags == b.tags
        assert a.aggregated_tags == b.aggregated_tags
        np.testing.assert_array_equal(a.timestamps, b.timestamps)
        np.testing.assert_allclose(a.values, b.values, rtol=1e-5,
                                   atol=1e-5)
    return got


class TestScanPathParity:
    @pytest.mark.parametrize("spec", [
        QuerySpec("m.cpu", {}, "sum", downsample=(600, "avg")),
        QuerySpec("m.cpu", {"host": "*"}, "avg", downsample=(600, "sum")),
        QuerySpec("m.cpu", {"dc": "east"}, "max", downsample=(300, "max")),
        QuerySpec("m.cpu", {"host": "h1|h2"}, "dev",
                  downsample=(600, "avg")),
        QuerySpec("m.cpu", {}, "sum", rate=True, downsample=(600, "avg")),
        QuerySpec("m.cpu", {}, "sum", rate=True, counter=True,
                  counter_max=2.0**32, downsample=(600, "avg")),
        QuerySpec("m.cpu", {}, "p95", downsample=(600, "avg")),
        QuerySpec("m.cpu", {"host": "*"}, "p95", downsample=(600, "avg")),
        QuerySpec("m.cpu", {"dc": "*"}, "p50", rate=True,
                  downsample=(600, "avg")),
        QuerySpec("m.cpu", {"host": "*"}, "zimsum",
                  downsample=(600, "sum")),
        QuerySpec("m.cpu", {"dc": "*", "host": "h3"}, "min",
                  downsample=(600, "min")),
    ], ids=lambda s: f"{s.aggregator}-{'rate' if s.rate else 'plain'}-"
                     f"{len(s.tags)}tags")
    def test_equals_scan_path(self, tsdb, spec):
        _load(tsdb)
        _compare(tsdb, spec)

    def test_partial_range(self, tsdb):
        """A sub-range query: range masking on device must match the
        scan path's [start, end] span trim."""
        _load(tsdb)
        _compare(tsdb, QuerySpec("m.cpu", {}, "sum",
                                 downsample=(300, "avg")),
                 start=BT + 1800, end=BT + 5400)

    def test_series_outside_range_do_not_shape_labels(self, tsdb):
        """A series with no points in the queried range must not appear
        in group labels (scan-path semantics: it is never seen)."""
        _load(tsdb, series=3, span=3600)
        # h9 exists only in hour 2
        tsdb.add_batch("m.cpu", BT + 7200 + np.arange(10) * 60,
                       np.arange(10.0), {"host": "h9", "dc": "west"})
        _compare(tsdb, QuerySpec("m.cpu", {}, "sum",
                                 downsample=(600, "avg")),
                 start=BT, end=BT + 3600)
        _compare(tsdb, QuerySpec("m.cpu", {"host": "*"}, "sum",
                                 downsample=(600, "avg")),
                 start=BT, end=BT + 3600)

    def test_no_matching_series_empty(self, tsdb):
        _load(tsdb, series=2)
        # 'h9' exists as a tag value (other metric) but no m.cpu series
        # carries it -> empty result, window hit, no scan.
        tsdb.add_batch("m.other", BT + np.arange(5) * 60,
                       np.arange(5.0), {"host": "h9", "dc": "east"})
        ex = QueryExecutor(tsdb, backend="tpu")
        h0 = tsdb.devwindow.window_hits
        out = ex.run(QuerySpec("m.cpu", {"host": "h9"}, "sum",
                               downsample=(600, "avg")), BT, BT + 7200)
        assert out == []
        assert tsdb.devwindow.window_hits > h0


class TestFallbacks:
    def test_undownsampled_falls_back(self, tsdb):
        _load(tsdb, series=2)
        _compare(tsdb, QuerySpec("m.cpu", {}, "sum"), expect_hit=False)

    def test_out_of_order_write_marks_dirty(self, tsdb):
        _load(tsdb, series=2)
        # rewrite an old timestamp for h0
        tsdb.add_point("m.cpu", BT + 1, 42.0,
                       {"host": "h0", "dc": "west"})
        assert tsdb.devwindow._metrics[
            tsdb.metrics.get_id("m.cpu")].dirty
        _compare(tsdb, QuerySpec("m.cpu", {}, "sum",
                                 downsample=(600, "avg")),
                 expect_hit=False)
        assert tsdb.devwindow.dirty_fallbacks >= 1

    def test_eviction_advances_coverage(self, tsdb):
        dw = DeviceWindow(staging_points=100, max_points=250)
        tsdb.devwindow = dw
        muid = b"\x00\x00\x01"
        for hour in range(5):
            dw.append(muid, b"skey",
                      BT + hour * 3600 + np.arange(100, dtype=np.int64),
                      np.ones(100, np.float32))
        dw.flush()
        assert dw.evicted_points > 0
        mw = dw._metrics[muid]
        assert mw.complete_from is not None
        # A query reaching before complete_from must miss...
        assert dw.columns(muid, BT, BT + 5 * 3600) is None
        # ...and one inside the kept window must hit.
        assert dw.columns(muid, mw.complete_from, BT + 5 * 3600) is not None

    def test_eviction_budget_is_global_across_metrics(self, tsdb):
        """max_points caps the SUM across metrics (the HBM budget is
        per chip): many metrics must not each claim a full budget."""
        dw = DeviceWindow(staging_points=100, max_points=350,
                          background=False)
        for m in range(4):
            dw.append(bytes([0, 0, m]), b"sk",
                      BT + np.arange(100, dtype=np.int64),
                      np.ones(100, np.float32))
            dw.flush()
        assert dw._total_points <= 350
        assert dw.evicted_points >= 50
        # the first metric's window lost its chunk -> coverage advanced
        assert dw._metrics[bytes([0, 0, 0])].complete_from is not None

    def test_mid_batch_throttle_invalidates_window(self, tsdb):
        """Rows applied before a PleaseThrottleError never reach the
        window; serving from it afterwards would silently drop them."""
        from opentsdb_tpu.core.errors import PleaseThrottleError

        _load(tsdb, series=2)
        muid = tsdb.metrics.get_id("m.cpu")
        orig = tsdb.store.put_many_columnar

        def throttling(*a, **k):
            e = PleaseThrottleError("full")
            e.partial_existed = []
            raise e

        tsdb.store.put_many_columnar = throttling
        try:
            with pytest.raises(PleaseThrottleError):
                tsdb.add_batch("m.cpu",
                               BT + 90000 + np.arange(5, dtype=np.int64),
                               np.arange(5.0), {"host": "h0",
                                                "dc": "west"})
        finally:
            tsdb.store.put_many_columnar = orig
        assert tsdb.devwindow.columns(muid, BT, BT + 7200) is None

    def test_timespan_beyond_int32_marks_dirty(self, tsdb):
        """>68 years from the metric's epoch would wrap the int32 rel
        column; the window must fall back, not mis-bucket."""
        dw = DeviceWindow(staging_points=10, background=False)
        muid = b"\x00\x00\x07"
        dw.append(muid, b"sk", np.arange(20, dtype=np.int64),
                  np.ones(20, np.float32))
        dw.append(muid, b"sk",
                  np.int64(2**31) + 100 + np.arange(20, dtype=np.int64),
                  np.ones(20, np.float32))
        dw.flush()
        assert dw._metrics[muid].dirty
        assert dw.columns(muid, 0, 2**31 + 200) is None

    def test_epoch_past_int32_query_falls_back(self, tsdb):
        """All-time query against a metric whose epoch is past 2^31:
        the devwindow shift (qbase - epoch) doesn't fit int32 and must
        fall back to the scan path instead of clamping (ADVICE r02
        medium); the scan path serves it via the float64 oracle."""
        from opentsdb_tpu.query.aggregators import Aggregators

        ts = np.int64(2**31) + 1000 + np.arange(50, dtype=np.int64) * 60
        tsdb.add_batch("m.late", ts, np.arange(50.0), {"host": "h0"})
        spec = QuerySpec("m.late", {}, "sum", downsample=(600, "avg"))
        ex = QueryExecutor(tsdb, backend="tpu")
        agg = Aggregators.get("sum")
        # Wide range: caught by the range-width guard before the window
        # is touched.
        assert ex.resident.serve(spec, 0, int(0xFFFFFFFF), agg) is None
        # Narrow range (fits int32) whose qbase is > 2^31 before the
        # metric's epoch: reaches the shift guard itself — the window
        # must fall back, not clamp.
        assert ex.resident.serve(spec, 0, 1000, agg) is None
        assert ex.run(spec, 0, 1000) == []
        got = ex.run(spec, 0, int(0xFFFFFFFF))
        want = QueryExecutor(tsdb, backend="cpu").run(
            spec, 0, int(0xFFFFFFFF))
        assert len(got) == len(want) == 1
        np.testing.assert_array_equal(got[0].timestamps,
                                      want[0].timestamps)
        np.testing.assert_allclose(got[0].values, want[0].values,
                                   rtol=1e-5)

    def test_upload_failure_frees_residency(self):
        """A failed device upload must run the full dirty-mark under the
        lock: the metric's resident chunks stop counting toward
        _total_points instead of holding HBM forever (ADVICE r02)."""
        dw = DeviceWindow(staging_points=10, background=False)
        a = b"\x00\x00\x01"
        dw.append(a, b"sk", BT + np.arange(20, dtype=np.int64),
                  np.ones(20, np.float32))
        assert dw._total_points == 20

        def boom(mw, batch, seq):
            raise RuntimeError("device gone")

        dw._upload = boom
        dw.append(a, b"sk", BT + 1000 + np.arange(20, dtype=np.int64),
                  np.ones(20, np.float32))
        mw = dw._metrics[a]
        assert mw.dirty
        assert dw._total_points == 0
        assert mw.inflight == 0
        assert dw.columns(a, BT, BT + 2000) is None

    def test_query_does_not_wait_on_other_metrics_uploads(self):
        """columns() waits only for ITS metric's in-flight uploads; a
        stuck upload of an unrelated metric must not stall the query
        (ADVICE r02: the global queue join coupled query latency to
        concurrent ingest bursts)."""
        import threading
        import time

        dw = DeviceWindow(staging_points=10, background=True)
        a, b = b"\x00\x00\x01", b"\x00\x00\x02"
        dw.append(a, b"ska", BT + np.arange(20, dtype=np.int64),
                  np.ones(20, np.float32))
        dw.flush()
        gate = threading.Event()
        orig = dw._upload

        def slow(mw, batch, seq):
            if mw is dw._metrics.get(b):
                gate.wait(8)
            return orig(mw, batch, seq)

        dw._upload = slow
        try:
            dw.append(b, b"skb", BT + np.arange(20, dtype=np.int64),
                      np.ones(20, np.float32))
            time.sleep(0.2)  # let the worker pick b's batch up and block
            # a gets more points, below the staging threshold: columns()
            # must upload them inline, not queue behind b's stuck batch.
            dw.append(a, b"ska", BT + 100 + np.arange(5, dtype=np.int64),
                      np.ones(5, np.float32))
            t0 = time.time()
            cols = dw.columns(a, BT, BT + 200)
            dt = time.time() - t0
        finally:
            gate.set()
        dw.flush()
        assert cols is not None
        assert int(np.asarray(cols.valid).sum()) == 25  # staged included
        assert dt < 3, f"query stalled {dt:.1f}s on another metric's upload"

    def test_invalidate_drops_metric(self, tsdb):
        _load(tsdb, series=2)
        muid = tsdb.metrics.get_id("m.cpu")
        assert tsdb.devwindow.columns(muid, BT, BT + 7200) is not None
        tsdb.devwindow.invalidate(muid)
        assert tsdb.devwindow.columns(muid, BT, BT + 7200) is None

    def test_mesh_executor_skips_window(self, tsdb):
        _load(tsdb, series=2)
        ex = QueryExecutor(tsdb, backend="tpu", mesh=object())
        assert ex.resident.serve(
            QuerySpec("m.cpu", {}, "sum", downsample=(600, "avg")),
            BT, BT + 7200, __import__(
                "opentsdb_tpu.query.aggregators",
                fromlist=["Aggregators"]).Aggregators.get("sum")) is None


class TestWarmup:
    def test_warm_from_existing_storage(self, tmp_path):
        """A restarted TSDB (WAL replay) must re-cover pre-existing data
        so the window serves history from before the process started."""
        from opentsdb_tpu.storage.kv import MemKVStore

        cfg = Config(auto_create_metrics=True, enable_sketches=False,
                     wal_path=str(tmp_path / "wal"))
        t1 = TSDB(MemKVStore(wal_path=cfg.wal_path), cfg,
                  start_compaction_thread=False)
        _load(t1, series=3)
        t1.shutdown()

        t2 = TSDB(MemKVStore(wal_path=cfg.wal_path), cfg,
                  start_compaction_thread=False)
        try:
            _compare(t2, QuerySpec("m.cpu", {"host": "*"}, "sum",
                                   downsample=(600, "avg")))
        finally:
            t2.compactionq.shutdown()


class TestStats:
    def test_counters_flow(self, tsdb):
        _load(tsdb, series=2)
        ex = QueryExecutor(tsdb, backend="tpu")
        ex.run(QuerySpec("m.cpu", {}, "sum", downsample=(600, "avg")),
               BT, BT + 7200)
        lines = []

        class C:
            def record(self, name, value, tag=None):
                lines.append((name, value))

        tsdb.collect_stats(C())
        names = {n for n, _ in lines}
        assert "devwindow.points.appended" in names
        assert "devwindow.hits" in names
        appended = dict(lines)["devwindow.points.appended"]
        assert appended == 2 * 200


def test_chunked_stage_matches_concat_stage():
    """window_series_stage_chunks over many small chunks must equal
    window_series_stage over the concatenated columns — same masks,
    same grids, same presence (the 1B-resident path is a pure
    implementation swap)."""
    from opentsdb_tpu.ops import kernels

    dw = DeviceWindow(staging_points=512, max_points=1 << 20,
                      background=False)
    rng = np.random.default_rng(3)
    muid = b"\x00\x00\x01"
    clocks = [1_700_000_000] * 5
    for batch in range(6):
        for s in range(5):
            n = 200
            ts = clocks[s] + np.cumsum(rng.integers(1, 60, n))
            clocks[s] = int(ts[-1]) + 1
            vals = rng.normal(50, 10, n).astype(np.float32)
            key = muid + b"\x00\x00\x01" + bytes([1 + s])
            dw.append(muid, key, ts.astype(np.int64), vals)
    dw.flush()
    start, end = 1_700_000_000, max(clocks) + 1
    ch = dw.chunk_columns(muid, start, end)
    cc = dw.columns(muid, start, end)
    assert ch is not None and cc is not None and len(ch.chunks) > 3
    assert ch.version == cc.version
    kw = dict(num_series=16, num_buckets=64, interval=600,
              agg_down="avg")
    lo = np.int32(0)
    hi = np.int32(end - cc.epoch)
    sh = np.int32(0)
    for agg, rate in (("avg", False), ("max", False), ("sum", True),
                      ("count", False), ("dev", False)):
        kw2 = dict(kw, agg_down=agg, rate=rate)
        a = kernels.window_series_stage_chunks(
            ch.chunks, lo, hi, sh, **kw2)
        b = kernels.window_series_stage(
            cc.rel_ts, cc.values, cc.sid, cc.valid, lo, hi, sh, **kw2)
        for ga, gb, name in zip(a, b,
                                ("sv", "sm", "filled", "ir", "pres")):
            ga, gb = np.asarray(ga), np.asarray(gb)
            if ga.dtype == bool:
                np.testing.assert_array_equal(
                    ga, gb, err_msg=f"{agg} rate={rate} {name}")
            else:
                np.testing.assert_allclose(
                    ga, gb, rtol=1e-5, atol=1e-5,
                    err_msg=f"{agg} rate={rate} {name}")


def test_wedged_uploader_degrades_instead_of_blocking():
    """A hung accelerator transport must not hang ingest or queries:
    once the uploader stalls past stall_timeout, appends dirty-mark the
    metric (sticky scan-path fallback) instead of blocking on the full
    queue, and queries waiting on an in-flight upload time out to the
    scan path. Found live in r03: a hung device transport froze a
    250M-point ingest run mid-flight."""
    import threading
    import time

    dw = DeviceWindow(staging_points=64, max_points=1 << 20,
                      stall_timeout=0.3)
    gate = threading.Event()
    real_upload = dw._run_upload

    def stuck_upload(work):
        gate.wait()             # simulates a hung device call
        real_upload(work)

    dw._run_upload = stuck_upload
    muid = b"\x00\x00\x01"
    key = muid + b"\x00\x00\x01\x00\x00\x02"
    ts0 = 1_700_000_000

    t0 = time.monotonic()
    for i in range(8):          # enough batches to fill queue + stall
        ts = np.arange(ts0 + i * 1000, ts0 + i * 1000 + 100,
                       dtype=np.int64)
        dw.append(muid, key, ts, np.ones(100, np.float32))
    ingest_wall = time.monotonic() - t0
    # Ingest proceeded: it waited out at most a few stall timeouts, not
    # forever (a blocking put would never return).
    assert ingest_wall < 5.0
    mw = dw._metrics[muid]
    assert mw.dirty and dw.upload_stalls >= 1
    # Queries: sticky degraded mode, IMMEDIATE scan fallback — the
    # dirty mark short-circuits the in-flight wait, and dropped work
    # items release their in-flight counts (no leak that would make
    # every later query pay a full stall_timeout).
    for _ in range(3):
        t0 = time.monotonic()
        assert dw.columns(muid, ts0, ts0 + 10_000) is None
        assert time.monotonic() - t0 < 0.1
    assert dw.dirty_fallbacks >= 3
    gate.set()                  # unblock the daemon thread


def test_slow_but_progressing_uploader_is_not_dirty_marked():
    """ADVICE r03: a backlogged-but-ALIVE uploader (each upload slower
    than stall_timeout's granularity but completing) must never trigger
    the sticky dirty mark — that turned a transient slowdown into a
    permanent loss of the metric's whole HBM window. Ingest applies
    backpressure; a query caught mid-backlog returns a bounded plain
    miss; once the backlog drains the window serves again."""
    import time

    dw = DeviceWindow(staging_points=64, max_points=1 << 20,
                      stall_timeout=2.0)
    real_upload = dw._run_upload

    def slow_upload(work):
        time.sleep(0.25)        # slower than queue turnover, << timeout
        real_upload(work)

    dw._run_upload = slow_upload
    muid = b"\x00\x00\x01"
    key = muid + b"\x00\x00\x01\x00\x00\x02"
    ts0 = 1_700_000_000
    for i in range(8):          # fills the bounded queue repeatedly
        ts = np.arange(ts0 + i * 1000, ts0 + i * 1000 + 100,
                       dtype=np.int64)
        dw.append(muid, key, ts, np.ones(100, np.float32))
    mw = dw._metrics[muid]
    assert not mw.dirty, "slow-but-progressing uploader was dirty-marked"
    assert dw.upload_stalls == 0
    # After the backlog drains, the window must serve (all 800 points).
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        with dw._cond:
            if mw.inflight == 0:
                break
        time.sleep(0.05)
    cols = dw.columns(muid, ts0, ts0 + 10_000)
    assert cols is not None and not mw.dirty
    assert mw.device_points == 800


def test_per_metric_stuck_upload_degrades_despite_global_progress():
    """The global liveness signal (any upload completing) must not mask
    a single metric whose own upload is wedged: other metrics' traffic
    keeps the transport 'alive', but after 4x stall_timeout without
    progress on ITS oldest in-flight batch the stuck metric converts to
    sticky dirty — otherwise every query of it would pay the 2x-cap
    slow-miss latency forever."""
    import threading
    import time

    dw = DeviceWindow(staging_points=1 << 20, max_points=1 << 20,
                      stall_timeout=0.3)
    gate = threading.Event()
    real_upload = dw._run_upload
    MUID_A, MUID_B = b"\x00\x00\x01", b"\x00\x00\x02"

    def upload(work):
        if work[0] is dw._metrics.get(MUID_A):
            gate.wait()         # only A's transfer is stuck
        real_upload(work)

    dw._run_upload = upload
    ts0 = 1_700_000_000
    keyA = MUID_A + b"\x00\x00\x01\x00\x00\x02"
    keyB = MUID_B + b"\x00\x00\x01\x00\x00\x02"
    dw.append(MUID_A, keyA, np.arange(ts0, ts0 + 100, dtype=np.int64),
              np.ones(100, np.float32))
    stop = threading.Event()

    def churn_b():
        i = 0
        while not stop.is_set():
            i += 1
            ts = np.arange(ts0 + i * 1000, ts0 + i * 1000 + 10,
                           dtype=np.int64)
            dw.append(MUID_B, keyB, ts, np.ones(10, np.float32))
            with dw._lock:
                w = dw._take_staged(dw._metrics[MUID_B])
            if w is not None:
                dw._submit(w)
            time.sleep(0.05)

    t = threading.Thread(target=churn_b, daemon=True)
    t.start()
    try:
        # Every query of A misses (helper-thread drain is gated); after
        # the per-metric deadline (4x stall_timeout = 1.2s) it must be
        # sticky-dirty despite B's completions resetting the global
        # wedge detector the whole time.
        mwA = None
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            assert dw.columns(MUID_A, ts0, ts0 + 10_000) is None
            mwA = dw._metrics[MUID_A]
            if mwA.dirty:
                break
        assert mwA is not None and mwA.dirty, \
            "stuck metric never degraded while global progress continued"
        # Sticky: immediate scan fallback from here on.
        t0 = time.monotonic()
        assert dw.columns(MUID_A, ts0, ts0 + 10_000) is None
        assert time.monotonic() - t0 < 0.1
    finally:
        stop.set()
        gate.set()


def test_wire_bf16_halves_payload_within_tolerance():
    """Config.wire_bf16 casts window-query [G, B] grids to float16 on
    device before the fetch (opt-in payload trade): results must
    match the exact path to float16 tolerance and identical
    masks/labels."""
    t = TSDB(MemKVStore(), Config(auto_create_metrics=True,
                                  enable_sketches=False,
                                  wire_bf16=True),
             start_compaction_thread=False)
    try:
        _load(t)
        ex = QueryExecutor(t, backend="tpu")
        spec = QuerySpec("m.cpu", {"host": "*"}, "p95",
                         downsample=(600, "avg"))
        h0 = t.devwindow.window_hits
        got = ex.run(spec, BT, BT + 7200)
        assert t.devwindow.window_hits > h0      # served by the window
        dw, t.devwindow = t.devwindow, None
        try:
            want = ex.run(spec, BT, BT + 7200)
        finally:
            t.devwindow = dw
        assert len(got) == len(want) and got
        for a, b in zip(got, want):
            assert a.tags == b.tags
            np.testing.assert_array_equal(a.timestamps, b.timestamps)
            np.testing.assert_allclose(a.values, b.values,
                                       rtol=1e-2, atol=1e-2)
        # Overflow regime: group sums far above float16's 65504 max
        # must stay finite (bfloat16 keeps float32's exponent range).
        for i in range(8):
            ts = BT + np.arange(100, dtype=np.int64) * 60
            t.add_batch("m.big", ts, np.full(100, 5e4), {"host": f"b{i}"})
        big = ex.run(QuerySpec("m.big", {}, "sum",
                               downsample=(600, "sum")), BT, BT + 7200)
        assert np.isfinite(big[0].values).all()
        assert big[0].values.max() > 65504 * 10
    finally:
        t.compactionq.shutdown()
