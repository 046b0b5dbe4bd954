"""A selective raw scan by seek and by walk: the same rows in the same
order from every tier shape, the same per-series columns, the same /q
bytes; which of the two a selector takes, and the counters that say so.

The store under test holds what the tier merge has to get right: two
sstable generations with overlapping hours, a frozen memtable, a live
one, a deleted row, a row tombstone with the row written again above
it, a series with no row in some hours and a series the directory
lists that has no row at all.
"""

import json

import numpy as np
import pytest

from opentsdb_tpu.core import codec
from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.storage import kv
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.storage.sharded import ShardedKVStore
from opentsdb_tpu.utils.config import Config
from tests.test_resident_tracing import q, serve, stat

BT = 1356998400
HOUR = 3600
HOSTS = 240
HOURS = 6
METRIC = "sk.cpu"
FAMILY = b"t"

SELECTORS = {
    "exact": {"host": "h001"},
    "list": {"host": "h003|h004|h007"},
    "two-tag": {"host": "h004|h005|h010", "dc": "d1"},
    "star": {"host": "*"},
}
# What each takes on the unsharded store: 1 or 3 hosts x 6 hours x 4
# tiers are few beside the range's keys; host=* names all of them.
SEEKS = {"exact": True, "list": True, "two-tag": True, "star": False}


def tags_of(h: int) -> dict:
    return {"host": f"h{h:03d}", "dc": f"d{h % 3}"}


def shards_of(store) -> list:
    return getattr(store, "shards", [store])


def freeze(store) -> None:
    """Checkpoint phase 1 by hand: the live memtable becomes the frozen
    tier under an empty live one."""
    for s in shards_of(store):
        with s._lock:
            s._frozen = s._tables
            s._tables = {n: type(t)() for n, t in s._frozen.items()}


def thaw(store) -> None:
    for s in shards_of(store):
        with s._lock:
            if s._frozen is not None:
                s._thaw_frozen_locked()


def build(root, shards: int, **cfg_kw) -> TSDB:
    cfg = Config(auto_create_metrics=True, port=0, bind="127.0.0.1",
                 device_window=False, backend="cpu", shards=shards,
                 enable_rollups=False, qcache_chunk_s=2 * HOUR, **cfg_kw)
    if shards > 1:
        store = ShardedKVStore(str(root / "store"), shards=shards)
    else:
        store = MemKVStore(wal_path=str(root / "store" / "wal"))
    tsdb = TSDB(store, cfg, start_compaction_thread=False)
    rng = np.random.default_rng(31)

    def put(hours, seconds, hosts=range(HOSTS), skip=()):
        for h in hosts:
            ts = np.concatenate([BT + HOUR * hr + seconds for hr in hours
                                 if (h, hr) not in skip])
            tsdb.add_batch(METRIC, ts.astype(np.int64),
                           np.round(rng.random(len(ts)) * 100, 2),
                           tags_of(h))

    first_half = np.arange(0, 1800, 60)
    second_half = np.arange(1800, 3600, 60)
    # Generation 1: hours 0-3; h007 has no row in hours 1 and 2.
    put(range(0, 4), first_half, skip={(7, 1), (7, 2)})
    tsdb.checkpoint()
    # Generation 2: hours 2-4, the other half of each row-hour, so the
    # rows of hours 2 and 3 lie in both generations.
    put(range(2, 5), second_half)
    tsdb.checkpoint()
    assert all(len(s._ssts) >= 2 for s in shards_of(store))
    t = tsdb.table
    # A row deleted whole, and one deleted and written again above its
    # tombstone; both tombstones end up in the frozen tier.
    store.delete_row(t, tsdb.row_key_for(METRIC, tags_of(3), BT + HOUR))
    store.delete_row(t, tsdb.row_key_for(METRIC, tags_of(4), BT + 2 * HOUR))
    put([2], first_half[:5], hosts=[4])
    put([4], first_half, hosts=range(0, HOSTS, 2))
    freeze(store)
    # Live: a new hour, cells over frozen and generation rows, and a
    # row tombstone over every lower tier.
    put([5], first_half, hosts=range(0, HOSTS, 3))
    put([3], np.arange(7, 600, 60), hosts=[1, 4, 10])
    store.delete_row(t, tsdb.row_key_for(METRIC, tags_of(5), BT))
    if tsdb.sketches is not None:
        # In the directory, never stored.
        tsdb.sketches.note_series(codec.series_key(tsdb.row_key_for(
            METRIC, {"host": "h999", "dc": "d1"}, BT)))
    return tsdb


@pytest.fixture(scope="module", params=[1, 4], ids=["unsharded", "shards4"])
def tsdb(request, tmp_path_factory):
    db = build(tmp_path_factory.mktemp("scan_seek"), request.param)
    yield db
    thaw(db.store)
    db.shutdown()


def selector(tsdb, tags: dict):
    """(start key, stop key, row-key regexp, hashes, series keys) of a
    selector over the whole stored span, as the executor forms them."""
    ex = QueryExecutor(tsdb, backend="cpu")
    uid = tsdb.metrics.get_id(METRIC)
    exact, group_bys = ex._tag_filters(tags)
    hint = ex._series_hint(uid, exact, group_bys)
    hashes, keys = hint["series_hint"], hint["series_keys"]
    return (uid + BT.to_bytes(4, "big"),
            uid + (BT + HOURS * HOUR).to_bytes(4, "big"),
            ex._build_regexp(exact, group_bys), hashes, keys)


def counts() -> tuple[float, float]:
    return stat("scan.seek"), stat("scan.walk")


@pytest.mark.parametrize("name", list(SELECTORS))
def test_scan_raw_by_seek_equals_the_walk(tsdb, name):
    lo, hi, regexp, hashes, keys = selector(tsdb, SELECTORS[name])
    seek0, walk0 = counts()
    walked = list(tsdb.store.scan_raw(tsdb.table, lo, hi, family=FAMILY,
                                      key_regexp=regexp,
                                      series_hint=hashes))
    seek1, walk1 = counts()
    sought = list(tsdb.store.scan_raw(tsdb.table, lo, hi, family=FAMILY,
                                      key_regexp=regexp, series_hint=hashes,
                                      series_keys=keys))
    seek2, walk2 = counts()
    assert sought == walked
    assert [k for k, _ in walked] == sorted(k for k, _ in walked)
    assert walked, "the selector must match stored rows"
    # Without the keys nothing seeks; every selective scan of a shard
    # the hint leaves in is counted once, one way or the other.
    n = len(shards_of(tsdb.store))
    scans = len({int(h) % n for h in hashes})
    assert (seek1 - seek0, walk1 - walk0) == (0, scans)
    assert (seek2 - seek1) + (walk2 - walk1) == scans
    if SEEKS[name]:
        assert (seek2 - seek1, walk2 - walk1) == (scans, 0)
    else:
        assert (seek2 - seek1, walk2 - walk1) == (0, scans)


@pytest.mark.parametrize("name", list(SELECTORS))
def test_scan_series_by_seek_equals_the_walk(tsdb, name):
    lo, hi, regexp, hashes, keys = selector(tsdb, SELECTORS[name])
    info_w, info_s = {}, {}
    skeys_w, walked = tsdb.scan_series(lo, hi, key_regexp=regexp,
                                       series_hint=hashes, counts=info_w)
    skeys_s, sought = tsdb.scan_series(lo, hi, key_regexp=regexp,
                                       series_hint=hashes, counts=info_s,
                                       series_keys=keys)
    assert skeys_s == skeys_w and info_s == info_w
    assert list(sought) == list(walked)
    for skey, cols in walked.items():
        for a, b in zip(cols, sought[skey]):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_what_the_store_holds(tsdb):
    """The shapes the parity cases rest on are really there."""
    lo, hi, regexp, hashes, keys = selector(tsdb, {"host": "*"})
    _skeys, per_series = tsdb.scan_series(lo, hi, key_regexp=regexp)

    def points(h, hour):
        skey = codec.series_key(tsdb.row_key_for(METRIC, tags_of(h), BT))
        ts = per_series[skey].timestamps - BT
        return int(((ts >= hour * HOUR) & (ts < (hour + 1) * HOUR)).sum())

    assert points(3, 1) == 0 and points(5, 0) == 0      # deleted
    assert points(3, 0) == 30 and points(5, 1) == 30
    assert points(4, 2) == 5                    # written again above
    assert points(7, 1) == 0 and points(7, 0) == 30     # a gap
    assert points(1, 3) == 30 + 30 + 10     # two generations + live
    assert points(0, 4) == 30 + 30 and points(0, 5) == 30   # frozen, live
    assert len(keys) == len(per_series) + 1             # the ghost
    assert all(s._frozen is not None for s in shards_of(tsdb.store))


def test_a_series_of_the_directory_alone_reads_as_nothing(tsdb):
    lo, hi, regexp, hashes, keys = selector(tsdb, {"host": "h999"})
    assert len(keys) == 1
    before = sum(counts())
    assert list(tsdb.store.scan_raw(tsdb.table, lo, hi, family=FAMILY,
                                    key_regexp=regexp, series_hint=hashes,
                                    series_keys=keys)) == []
    # The blooms leave its one shard's memtables alone, and those hold
    # few keys: whichever way that scan goes, it is counted once.
    assert sum(counts()) - before == 1


@pytest.mark.parametrize("lo_off,hi_off", [(0, 1), (1, 3), (2, 6), (5, 9)])
def test_any_chunk_of_the_range(tsdb, lo_off, hi_off):
    """Fragment chunks: sub-ranges, one of them past the stored span."""
    _lo, _hi, regexp, hashes, keys = selector(tsdb, SELECTORS["list"])
    uid = tsdb.metrics.get_id(METRIC)
    lo = uid + (BT + lo_off * HOUR).to_bytes(4, "big")
    hi = uid + (BT + hi_off * HOUR).to_bytes(4, "big")
    walked = list(tsdb.store.scan_raw(tsdb.table, lo, hi, key_regexp=regexp))
    sought = list(tsdb.store.scan_raw(tsdb.table, lo, hi, key_regexp=regexp,
                                      series_keys=keys))
    assert sought == walked and all(lo <= k < hi for k, _ in sought)


@pytest.mark.parametrize("bounds", ["short", "long", "two-metrics", "open"])
def test_bounds_that_are_no_base_hours_walk(tsdb, bounds):
    lo, hi, regexp, hashes, keys = selector(tsdb, SELECTORS["exact"])
    lo, hi = {"short": (lo[:3], hi),
              "long": (lo, hi + b"\x00"),
              "two-metrics": (lo, b"\xff\xff\xff" + hi[3:]),
              "open": (lo, b"")}[bounds]
    seek0, walk0 = counts()
    sought = list(tsdb.store.scan_raw(tsdb.table, lo, hi, key_regexp=regexp,
                                      series_keys=keys))
    assert counts()[0] == seek0 and counts()[1] > walk0
    assert sought == list(tsdb.store.scan_raw(tsdb.table, lo, hi,
                                              key_regexp=regexp))
    assert sought


def test_no_regexp_is_no_selective_scan(tsdb):
    lo, hi, _regexp, hashes, keys = selector(tsdb, {})
    before = counts()
    rows = list(tsdb.store.scan_raw(tsdb.table, lo, hi, series_hint=hashes,
                                    series_keys=keys))
    assert counts() == before and rows


@pytest.mark.parametrize("name", list(SELECTORS))
def test_the_executor_hands_the_keys_down(tsdb, name):
    """A query through the fragment cache: answers equal the walk's,
    cold and warm, and the narrow selectors seek."""
    ex = QueryExecutor(tsdb, backend="cpu")
    spec = QuerySpec(METRIC, SELECTORS[name], "max",
                     downsample=(300, "max"))
    start, end = BT + 7, BT + HOURS * HOUR - 1
    seek0, walk0 = counts()
    cold = ex.run(spec, start, end)
    seek1, walk1 = counts()
    warm = ex.run(spec, start, end)
    assert (seek1 - seek0 > 0) == SEEKS[name]
    assert (walk1 - walk0 > 0) == (not SEEKS[name])
    seek2, _ = counts()     # dirty chunks are scanned again when warm
    sk, tsdb.sketches = tsdb.sketches, None     # no directory: the walk
    try:
        oracle = QueryExecutor(tsdb, backend="cpu").run(spec, start, end)
    finally:
        tsdb.sketches = sk
    assert counts()[0] == seek2
    assert len(oracle) == len(cold) == len(warm) > 0
    for got in (cold, warm):
        for g, o in zip(got, oracle):
            assert g.tags == o.tags
            assert np.array_equal(g.timestamps, o.timestamps)
            assert np.array_equal(g.values, o.values)


@pytest.mark.parametrize("why", ["replica", "no-sketches"])
def test_without_a_complete_directory_the_scan_walks(tmp_path, why):
    """A replica's directory can lag its store, and a daemon without
    sketches has none: neither is handed a hint, so both walk, and
    answer what the writer that seeks answers."""
    spec = QuerySpec(METRIC, SELECTORS["list"], "sum")
    span = (BT, BT + HOURS * HOUR)
    writer = build(tmp_path / "writer", 1)
    try:
        thaw(writer.store)
        seek0, _ = counts()
        expect = QueryExecutor(writer, backend="cpu").run(spec, *span)
        assert counts()[0] > seek0
        if why == "replica":
            writer.checkpoint()
            reader = TSDB(
                MemKVStore(wal_path=str(tmp_path / "writer/store/wal"),
                           read_only=True),
                Config(device_window=False, backend="cpu",
                       enable_rollups=False),
                start_compaction_thread=False)
        else:
            reader = build(tmp_path / "plain", 1, enable_sketches=False)
        try:
            seek1, walk1 = counts()
            got = QueryExecutor(reader, backend="cpu").run(spec, *span)
            assert counts()[0] == seek1 and counts()[1] > walk1
        finally:
            thaw(reader.store)
            reader.shutdown()
    finally:
        writer.shutdown()
    assert len(got) == len(expect) == 3
    for g, e in zip(got, expect):
        assert g.tags == e.tags
        assert np.array_equal(g.timestamps, e.timestamps)
        assert np.array_equal(g.values, e.values)


@pytest.mark.parametrize("name", list(SELECTORS))
def test_q_through_the_raw_plan_answers_the_same_bytes(tsdb, name,
                                                       monkeypatch):
    tag = ",".join(f"{k}={v}" for k, v in SELECTORS[name].items())
    target = q(BT + 11, BT + HOURS * HOUR - 1,
               f"max:5m-max:{METRIC}{{{tag}}}", trace=False)
    seek0, _ = counts()
    (st_s, sought), = serve(tsdb, target)
    seek1, walk1 = counts()
    # No probe count is small enough: every scan walks.
    monkeypatch.setattr(kv, "_SEEK_MARGIN", 1 << 62)
    (st_w, walked), = serve(tsdb, target)
    assert counts()[0] == seek1 and counts()[1] > walk1
    assert (seek1 - seek0 > 0) == SEEKS[name]
    assert st_s == st_w == 200 and sought == walked
    assert b'"rollup": "raw"' in sought or b'"rollup":"raw"' in sought


def test_the_span_says_which_way(tsdb):
    target = q(BT + 11, BT + HOURS * HOUR - 1,
               f"max:5m-max:{METRIC}{{host=h002}}")
    (st, body), = serve(tsdb, target)
    assert st == 200
    scan = next(s for s in json.loads(body)[0]["trace"]["spans"]
                if s["name"] == "scan")
    decodes = [s for s in scan["spans"] if s["name"] == "chunk.decode"]
    assert decodes
    # One host's shard alone is scanned, and seeks; the six hours of
    # the request are six candidate keys, however they are chunked.
    assert all(s["tags"]["seek"] == 1 for s in decodes)
    assert sum(s["tags"]["probes"] for s in decodes) == HOURS
