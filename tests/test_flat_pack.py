"""A raw request's point stream written straight from the scan's flat
blocks: the stream holds what the per-series spans hold, the answers
are the oracle's, the counters say where the points were read from, and
the callers that want per-series columns still get a whole-range scan's.

The store under test holds what the fragments have to get right: two
sstable generations with overlapping hours, a live memtable (so the
range's last chunk is dirty and bypasses the fragment cache while the
others hit), a series with a gap, a series absent from one whole
chunk, and a series the directory lists that has no row at all.
"""

import json

import numpy as np
import pytest

from opentsdb_tpu.compress.devcache import pad_fine
from opentsdb_tpu.core import codec
from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec, _Scan
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.storage.sharded import ShardedKVStore
from opentsdb_tpu.utils.config import Config
from tests.test_resident_tracing import q, serve, stat, walk

BT = 1356998400
HOUR = 3600
HOSTS = 24
HOURS = 6
CHUNK = 2 * HOUR
METRIC = "fp.cpu"
GAP, ABSENT = 7, 10     # no row in hours 1-2; none in the chunk of hours 2-3

SELECTORS = {
    "one-group": {"dc": "d1"},
    "eight-groups": {"host": "|".join(f"h{h:03d}" for h in range(4, 12))},
    "every-host": {"host": "*"},
}
# Windows over the three 2 h chunks: from an arbitrary second across all
# three; inside the first; the clean second and the dirty third.
WINDOWS = {
    "three-chunks": (BT + 1234, BT + 5 * HOUR + 1777),
    "inside-one": (BT + 601, BT + HOUR + 1500),
    "clean-and-dirty": (BT + 2 * HOUR + 59, BT + HOURS * HOUR - 1),
}
EXACT = ("max", "min", "count")


def tags_of(h: int) -> dict:
    return {"host": f"h{h:03d}", "dc": f"d{h % 3}"}


def build(root, shards: int, **cfg_kw) -> TSDB:
    kw = dict(auto_create_metrics=True, port=0, bind="127.0.0.1",
              device_window=False, backend="tpu", shards=shards,
              enable_rollups=False, qcache_chunk_s=CHUNK)
    kw.update(cfg_kw)
    if shards > 1:
        store = ShardedKVStore(str(root / "store"), shards=shards)
    else:
        store = MemKVStore(wal_path=str(root / "store" / "wal"))
    tsdb = TSDB(store, Config(**kw), start_compaction_thread=False)
    rng = np.random.default_rng(35)

    def put(hours, seconds, hosts=range(HOSTS), skip=()):
        for h in hosts:
            ts = np.concatenate([BT + HOUR * hr + seconds for hr in hours
                                 if (h, hr) not in skip])
            # Two decimals that a float32 holds, so that max / min come
            # back from the f32 kernels bit for bit.
            vals = np.round(rng.random(len(ts)) * 100, 2).astype(np.float32)
            tsdb.add_batch(METRIC, ts.astype(np.int64),
                           vals.astype(np.float64), tags_of(h))

    skip = {(GAP, 1), (GAP, 2), (ABSENT, 2), (ABSENT, 3)}
    # Generation 1: hours 0-3, the first half of each row-hour.
    put(range(0, 4), np.arange(0, 1800, 60), skip=skip)
    tsdb.checkpoint()
    # Generation 2: hours 2-4, the other half, so the rows of hours 2
    # and 3 lie in both generations.
    put(range(2, 5), np.arange(1800, 3600, 60), skip=skip)
    tsdb.checkpoint()
    # Live: hour 5 of every third host. The chunk of hours 4-5 is dirty.
    put([5], np.arange(0, 1800, 60), hosts=range(0, HOSTS, 3))
    if tsdb.sketches is not None:
        # In the directory, never stored.
        tsdb.sketches.note_series(codec.series_key(tsdb.row_key_for(
            METRIC, {"host": "h999", "dc": "d1"}, BT)))
    return tsdb


@pytest.fixture(scope="module", params=[1, 4], ids=["unsharded", "shards4"])
def tsdb(request, tmp_path_factory):
    db = build(tmp_path_factory.mktemp("flat_pack"), request.param)
    yield db
    db.shutdown()


def scan_of(ex, tags, start, end, info=None) -> _Scan:
    return ex._find_series(QuerySpec(METRIC, tags), start, end, info)


def whole_range(tsdb, tags) -> dict:
    """Per-series columns of one uncached scan of the stored span."""
    ex = QueryExecutor(tsdb, backend="cpu")
    uid = tsdb.metrics.get_id(METRIC)
    exact, group_bys = ex._tag_filters(tags)
    return tsdb.scan_series(
        uid + BT.to_bytes(4, "big"),
        uid + (BT + HOURS * HOUR).to_bytes(4, "big"),
        key_regexp=ex._build_regexp(exact, group_bys))[1]


def test_what_the_store_holds(tsdb):
    """The shapes the cases rest on are really there."""
    ex = QueryExecutor(tsdb, backend="tpu")
    ex._frag_cache.clear()
    start, end = WINDOWS["three-chunks"]
    scan_of(ex, {"host": "*"}, start, end)              # cold: fills
    hits, byp = ex.qcache_hits, ex.qcache_bypasses
    scan = scan_of(ex, {"host": "*"}, start, end)
    assert (ex.qcache_hits - hits, ex.qcache_bypasses - byp) == (2, 1)
    assert len(scan.blocks) == 3 and len(scan.keys) == HOSTS
    key = {h: codec.series_key(tsdb.row_key_for(METRIC, tags_of(h), BT))
           for h in (0, GAP, ABSENT)}
    held = [set(b.per_series()) for b in scan.blocks]
    assert key[ABSENT] in held[0] and key[ABSENT] not in held[1]
    assert all(key[GAP] in h for h in held)
    per = whole_range(tsdb, {"host": "*"})
    hours = lambda h: set(
        ((per[key[h]].timestamps - BT) // HOUR).tolist())
    assert hours(GAP) == {0, 3, 4} and hours(ABSENT) == {0, 1, 4}
    assert hours(0) == set(range(HOURS))
    assert len(per) == HOSTS                            # no ghost


@pytest.mark.parametrize("name", list(SELECTORS) + ["exact"])
def test_per_series_columns_are_a_whole_range_scans(tsdb, name):
    """What the rollup planner's stitch and the oracle's callers are
    handed: views of the merged fragments, equal to one uncached scan
    column for column and in its order, cold and warm."""
    tags = SELECTORS.get(name, {"host": f"h{GAP:03d}"})
    ex = QueryExecutor(tsdb, backend="cpu")
    ex._frag_cache.clear()
    uid = tsdb.metrics.get_id(METRIC)
    exact, group_bys = ex._tag_filters(tags)
    regexp = ex._build_regexp(exact, group_bys)
    want = whole_range(tsdb, tags)
    assert want
    for _ in ("cold", "warm"):
        got = ex._scan_selector(uid, exact, group_bys, regexp, BT,
                                BT + HOURS * HOUR - 1)
        assert list(got) == list(want)
        for skey, cols in want.items():
            for a, b in zip(cols, got[skey]):
                assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("name", list(SELECTORS))
def test_the_stream_holds_what_the_spans_hold(tsdb, name, window):
    """The stream written block by block against the stream the spans
    of the stitched series give: the same (series, time, value) points
    under the same padded length, each series' in ascending time."""
    start, end = WINDOWS[window]
    interval = 300
    qbase = start - start % interval
    ex = QueryExecutor(tsdb, backend="tpu")
    for _ in ("cold", "warm"):
        info = {}
        scan = scan_of(ex, SELECTORS[name], start, end, info)
        rel, vals, sid, valid = scan.stream(qbase, pad=True)
        spans = [sp for g in scan.spans().values() for sp in g]
        assert spans
        n = sum(len(sp.timestamps) for sp in spans)
        assert info["points"] == scan.points == n == int(valid.sum())
        assert len(rel) == len(vals) == len(sid) == pad_fine(n)
        assert valid[:n].all() and not valid[n:].any()
        assert rel.dtype == sid.dtype == np.int32
        assert vals.dtype == np.float32 and valid.dtype == bool
        keys = np.asarray(scan.keys, dtype=object)
        got = sorted(zip(keys[sid[:n]].tolist(), rel[:n].tolist(),
                         vals[:n].tolist()))
        want = sorted(
            (sp.series_key, int(t) - qbase, float(np.float32(v)))
            for sp in spans
            for t, v in zip(sp.timestamps.tolist(), sp.values.tolist()))
        assert got == want
        # Whatever order the blocks lie in, a (series, bucket) segment
        # meets its points oldest first.
        for s in range(len(scan.keys)):
            assert (np.diff(rel[:n][sid[:n] == s]) > 0).all()
        assert start - qbase <= rel[:n].min() and rel[:n].max() <= end - qbase
        # Unpadded, for the mesh packers: the same points and no more.
        flat = scan.stream(qbase)
        assert all(np.array_equal(a[:n], b) for a, b in zip(
            (rel, vals, sid, valid), flat))


def assert_results(got, want, exact):
    assert [r.tags for r in got] == [r.tags for r in want]
    assert len(got) > 0
    for g, w in zip(got, want):
        assert g.aggregated_tags == w.aggregated_tags
        np.testing.assert_array_equal(g.timestamps, w.timestamps)
        if exact:
            np.testing.assert_array_equal(g.values, w.values)
        else:
            np.testing.assert_allclose(g.values, w.values, rtol=1e-5)


@pytest.mark.parametrize("window", ["three-chunks", "inside-one"])
@pytest.mark.parametrize("agg", ["max", "min", "count", "avg", "sum"])
@pytest.mark.parametrize("name", list(SELECTORS))
def test_answers_are_the_oracles(tsdb, name, agg, window):
    """The fused kernels fed from the blocks against the float64 oracle
    fed from the spans, cold and warm."""
    spec = QuerySpec(METRIC, SELECTORS[name], agg, downsample=(300, agg))
    start, end = WINDOWS[window]
    want = QueryExecutor(tsdb, backend="cpu").run(spec, start, end)
    ex = QueryExecutor(tsdb, backend="tpu")
    ex._frag_cache.clear()
    groups = {"one-group": 1, "eight-groups": 8, "every-host": HOSTS}
    assert len(want) == groups[name]
    # A group of several series lerps over its members' gaps, in f32 on
    # the device; a group of one is its series' buckets as reduced.
    exact = agg in EXACT and name != "one-group"
    for _ in ("cold", "warm"):
        assert_results(ex.run(spec, start, end), want, exact)


@pytest.mark.parametrize("name", list(SELECTORS))
@pytest.mark.parametrize("spec_kw", [
    dict(aggregator="sum", rate=True, downsample=(600, "avg")),
    dict(aggregator="p95", downsample=(600, "max")),
    dict(aggregator="p50", rate=True, downsample=(600, "avg")),
    dict(aggregator="zimsum", downsample=(900, "sum")),
], ids=["rate", "p95", "p50-rate", "zimsum"])
def test_rate_and_percentiles_ride_the_same_stream(tsdb, name, spec_kw):
    spec = QuerySpec(METRIC, SELECTORS[name], **spec_kw)
    start, end = WINDOWS["clean-and-dirty"]
    want = QueryExecutor(tsdb, backend="cpu").run(spec, start, end)
    got = QueryExecutor(tsdb, backend="tpu").run(spec, start, end)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g.tags == w.tags
        np.testing.assert_array_equal(g.timestamps, w.timestamps)
        np.testing.assert_allclose(g.values, w.values, rtol=2e-4,
                                   atol=1e-4)


def counters():
    return (stat("query.pack.flat_points"), stat("query.pack.span_points"),
            stat("query.raw.points"))


@pytest.mark.parametrize("name", list(SELECTORS))
def test_q_bodies_spans_and_counters(tsdb, name):
    """/q through the server: the same bytes cold and warm, the spans'
    tags as they were, and every point packed counted as read flat."""
    tag = ",".join(f"{k}={v}" for k, v in SELECTORS[name].items())
    start, end = WINDOWS["three-chunks"]
    m = f"avg:5m-avg:{METRIC}{{{tag}}}"
    QueryExecutor(tsdb)._frag_cache.clear()
    flat0, spans0, raw0 = counters()
    (st_c, cold), (st_w, warm) = serve(
        tsdb, q(start, end, m, trace=False), q(start, end, m, trace=False))
    assert st_c == st_w == 200 and cold == warm
    per = whole_range(tsdb, SELECTORS[name])
    points = sum(int(((c.timestamps >= start) & (c.timestamps <= end)).sum())
                 for c in per.values())
    flat1, spans1, raw1 = counters()
    assert (flat1 - flat0, spans1 - spans0, raw1 - raw0) == (
        2 * points, 0, 2 * points)
    (st, body), = serve(tsdb, q(start, end, m))
    assert st == 200
    out = json.loads(body)
    strip = lambda rs: [{k: v for k, v in r.items() if k != "trace"}
                        for r in rs]
    assert strip(out) == strip(json.loads(warm))
    spans = {s["name"]: s for s in walk(out[0]["trace"])}
    series = len(per)
    assert spans["scan"]["tags"]["points"] == points
    assert spans["scan"]["tags"]["rows"] > 0        # the dirty chunk
    assert spans["scan"]["tags"]["cached"] is False
    assert spans["scan.group"]["tags"] == {"series": series,
                                           "groups": len(out)}
    pack = spans["aggregate.pack"]["tags"]
    assert pack == {"series": series, "slots": pad_fine(points)}
    assert counters() == (flat1 + points, spans1, raw1 + points)


def test_a_series_with_no_point_in_range_forms_no_group(tsdb):
    """Hour 2: ABSENT is not in the chunk's block at all, GAP is (its
    hour 3) with no row in range."""
    ex = QueryExecutor(tsdb, backend="tpu")
    spec = QuerySpec(METRIC, {"host": "*"}, "max", downsample=(300, "max"))
    window = (BT + 2 * HOUR, BT + 3 * HOUR - 1)
    got = ex.run(spec, *window)
    assert {r.tags["host"] for r in got} == {
        f"h{h:03d}" for h in range(HOSTS) if h not in (GAP, ABSENT)}
    scan = scan_of(ex, {"host": "*"}, *window)
    (block,), (sids,) = scan.blocks, scan.sids
    assert len(block.series_keys) == HOSTS - 1
    assert len(scan.keys) == HOSTS - 2 == len(scan.groups)
    assert sorted(sids.tolist()) == [-1] + list(range(HOSTS - 2))
    assert_results(got, QueryExecutor(tsdb, backend="cpu").run(
        spec, *window), exact=True)
    # Nothing at all in range: no group, no kernel call, no result.
    assert ex.run(spec, BT + 40 * HOUR, BT + 41 * HOUR) == []


def test_an_all_memtable_range_goes_flat_too(tmp_path):
    """A store that never checkpointed: every chunk is dirty, the scan
    is one unchunked block, and the request still packs from it."""
    tsdb = TSDB(MemKVStore(), Config(auto_create_metrics=True,
                                     device_window=False,
                                     enable_rollups=False, backend="tpu"),
                start_compaction_thread=False)
    try:
        ts = BT + np.arange(0, 3 * HOUR, 30, dtype=np.int64)
        for h in range(5):
            tsdb.add_batch(METRIC, ts, np.arange(len(ts)) % 17 + h,
                           tags_of(h))
        spec = QuerySpec(METRIC, {"host": "*"}, "sum",
                         downsample=(600, "max"))
        ex = QueryExecutor(tsdb, backend="tpu")
        flat0, spans0, _ = counters()
        got = ex.run(spec, BT + 100, BT + 2 * HOUR)
        assert ex.qcache_bypasses > 0 and ex.qcache_hits == 0
        n = 5 * len(ts[(ts >= BT + 100) & (ts <= BT + 2 * HOUR)])
        assert counters()[:2] == (flat0 + n, spans0)
        assert_results(got, QueryExecutor(tsdb, backend="cpu").run(
            spec, BT + 100, BT + 2 * HOUR), exact=False)
    finally:
        tsdb.shutdown()


def test_span_lists_still_pack_and_are_counted_apart(tsdb):
    """What does not come from a scan's blocks (the rollup planner's
    per-bucket records, the mesh packers' one group) goes through the
    same writer as one block, and is counted under span_points."""
    ex = QueryExecutor(tsdb, backend="tpu")
    start, end = WINDOWS["three-chunks"]
    groups = ex._find_spans(QuerySpec(METRIC, {"host": "*"}), start, end)
    assert len(groups) == HOSTS
    flat0, spans0, _ = counters()
    scan = _Scan.of_spans(groups)
    n = sum(len(sp.timestamps) for g in groups.values() for sp in g)
    assert scan.points == n and list(scan.groups) == list(groups)
    rel, vals, sid, valid = scan.stream(start, pad=True)
    assert len(rel) == pad_fine(n) and int(valid.sum()) == n
    at = 0
    for members in groups.values():
        for sp in members:
            k = len(sp.timestamps)
            assert np.array_equal(rel[at:at + k], sp.timestamps - start)
            assert np.array_equal(vals[at:at + k],
                                  sp.values.astype(np.float32))
            assert (sid[at:at + k] == scan.keys.index(sp.series_key)).all()
            at += k
    again = scan.spans()
    for gkey, members in groups.items():
        for a, b in zip(members, again[gkey]):
            assert a.series_key == b.series_key and a.tags == b.tags
            assert np.array_equal(a.timestamps, b.timestamps)
            assert np.array_equal(a.values, b.values)
    one = next(iter(groups.values()))
    r1, v1, s1, ok1 = _Scan.of_spans({(): one}).stream(start)
    assert len(r1) == len(one[0].timestamps) and ok1.all()
    assert (s1 == 0).all() and v1.dtype == np.float32
    assert counters()[:2] == (flat0, spans0 + n + len(r1))


def test_a_rollup_served_request_counts_under_span_points(tmp_path):
    from tests.test_rollup import BASE, METRIC as ROLL, ingest, make_tsdb
    tsdb = make_tsdb(str(tmp_path), backend="tpu")
    try:
        ingest(tsdb, series=4, days=2)
        tsdb.checkpoint()
        ex = QueryExecutor(tsdb, backend="tpu")
        spec = QuerySpec(ROLL, {"host": "*"}, "sum",
                         downsample=(3600, "sum"))
        flat0, spans0, _ = counters()
        got, plan, _ = ex.run_with_plan(spec, BASE, BASE + 86400 - 1)
        assert plan == "1h" and len(got) == 4
        flat1, spans1, _ = counters()
        assert flat1 == flat0 and spans1 - spans0 == 4 * 24
    finally:
        tsdb.shutdown()


def test_block_cut_is_a_searchsorted_a_series():
    """SeriesBlock.cut against numpy's own binary search, on ragged
    series with empty ones among them and bounds that fall on, between
    and outside the timestamps."""
    rng = np.random.default_rng(5)
    lens = [0, 1, 7, 0, 64, 3, 129, 0]
    cols = [np.sort(rng.choice(1000, n, replace=False)).astype(np.int64)
            for n in lens]
    bounds = np.concatenate(([0], np.cumsum(lens))).astype(np.int64)
    ts = np.concatenate(cols)
    blk = codec.SeriesBlock(
        [bytes([i]) for i in range(len(lens))], bounds,
        codec.Columns(ts, ts.astype(np.float64), ts, ts > 0))
    for start, end in [(0, 999), (250, 750), (500, 500), (-5, -1),
                       (1000, 2000), (int(ts[10]), int(ts[40])),
                       (700, 300)]:
        lo, hi = blk.cut(start, end)
        for i, c in enumerate(cols):
            a = int(np.searchsorted(c, start, "left"))
            b = max(a, int(np.searchsorted(c, end, "right")))
            assert (lo[i] - bounds[i], hi[i] - bounds[i]) == (a, b)
    assert codec.SeriesBlock.merged([blk]) is blk
    two = codec.SeriesBlock.merged([blk, blk._replace(cols=codec.Columns(
        ts + 1000, ts.astype(np.float64), ts, ts > 0))])
    assert two.series_keys == blk.series_keys
    assert np.array_equal(np.diff(two.bounds), 2 * np.diff(bounds))
    for i, c in enumerate(cols):
        got = two.cols.timestamps[two.bounds[i]:two.bounds[i + 1]]
        assert np.array_equal(got, np.concatenate((c, c + 1000)))


def synthetic_block(layout: str, rng) -> codec.SeriesBlock:
    """Seven series of sorted timestamps in [0, 1000): sampled in step
    (``grid``), each on its own times and of its own length with an
    empty one among them (``ragged``), or one alone (``one``)."""
    if layout == "grid":
        cols = [np.arange(5, 1000, 20, dtype=np.int64)] * 7
    elif layout == "one":
        cols = [np.arange(3, 1000, 7, dtype=np.int64)]
    else:
        cols = [np.sort(rng.choice(1000, n, replace=False)).astype(np.int64)
                for n in (40, 1, 0, 77, 13, 200, 5)]
    bounds = np.concatenate(([0], np.cumsum([len(c) for c in cols])))
    ts = np.concatenate(cols)
    return codec.SeriesBlock(
        [layout.encode() + bytes([i]) for i in range(len(cols))],
        bounds.astype(np.int64),
        codec.Columns(ts, rng.random(len(ts)) * 100, ts, ts > 0))


@pytest.mark.parametrize("window", [(0, 999), (250, 750), (433, 433),
                                    (990, 2000), (2000, 3000)])
@pytest.mark.parametrize("layouts", [("grid",), ("ragged",), ("one",),
                                     ("grid", "ragged", "grid", "one")])
def test_a_run_a_grid_and_a_ragged_cut_give_the_same_stream(layouts, window):
    """stream() reads a block's rows in range as one run, as a 2-D
    view, or through an index vector, by what the cut looks like: each
    against a loop over the series."""
    rng = np.random.default_rng(len(layouts) + window[0])
    blocks = [synthetic_block(name, rng) for name in layouts]
    start, end = window
    scan = _Scan(blocks, start, end)
    want, keys = [], []
    for blk in blocks:
        for skey, cols in blk.per_series().items():
            m = (cols.timestamps >= start) & (cols.timestamps <= end)
            if m.any() and skey not in keys:
                keys.append(skey)
            want += [(keys.index(skey), int(t) - 100, np.float32(v))
                     for t, v in zip(cols.timestamps[m], cols.values[m])]
    assert scan.keys == keys and scan.points == len(want)
    for pad in (False, True):
        rel, vals, sid, valid = scan.stream(100, pad=pad)
        n = len(want)
        assert len(rel) == (pad_fine(n) if pad else n)
        assert list(zip(sid[:n].tolist(), rel[:n].tolist(), vals[:n])) == want
        assert valid[:n].all() and not valid[n:].any()
        assert not rel[n:].any() and not vals[n:].any() and not sid[n:].any()
