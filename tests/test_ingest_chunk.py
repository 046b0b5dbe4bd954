"""A wire chunk is ONE multi-series put (server/wire.ingest_batch ->
TSDB.add_chunk -> one put_many_columnar a key length -> one
DeviceWindow.append_many a metric -> one sketch observation), and it
leaves what the plain reference (tests/ingest_reference.py: one
add_batch a series) leaves: the same rows byte for byte, the same rows
queued for compaction, the same error strings, accounts, device-window
columns and sketch answers; the same store after a WAL replay, and with
the last record torn the whole chunk absent."""

import json
import os
import shutil

import numpy as np
import pytest

from opentsdb_tpu.cluster import epoch as cepoch
from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.server import wire
from opentsdb_tpu.stats import livesketch
from opentsdb_tpu.stats.livesketch import LiveSketches
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.storage.sharded import ShardedKVStore
from opentsdb_tpu.utils.config import Config
from tests.ingest_reference import ingest_batch_reference
from tests.test_resident_tracing import q, serve, stat

BT = 1356998400                      # an hour's start
METRICS = ("cpu.user", "cpu.system", "cpu.idle", "mem.free")
SEEDS = (11, (1 << 31) + 38)


def series_of(rng, n_hosts: int) -> list[tuple[str, str]]:
    """(metric, tags text) of the chunk's series: eight tags a host, and
    every fifth host three (a second row-key length)."""
    out = []
    for h in range(n_hosts):
        tags = (f"host=h{h} dc=d{h % 3} rack=r{h % 7}" if h % 5 == 0 else
                f"host=h{h} region=eu dc=d{h % 3} rack=r{h % 7} os=linux "
                f"arch=x64 team=t{h % 4} service=s{h % 6}")
        for m in METRICS:
            out.append((m, tags))
    order = rng.permutation(len(out))
    return [out[i] for i in order]


def value_text(rng) -> str:
    kind = rng.integers(4)
    if kind == 0:
        return f"{rng.random() * 100:.2f}"
    if kind == 1:
        return str(int(rng.integers(-300, 70000)))
    if kind == 2:                     # past 2^53: float64 cannot hold it
        return str((1 << 53) + 1 + int(rng.integers(1 << 20)))
    return f"{rng.random():.6e}"


def chunk(rng, series, step: int) -> bytes:
    """One wire chunk: 1-3 points a series at and after ``step``."""
    lines = []
    for metric, tags in series:
        for k in range(int(rng.integers(1, 4))):
            ts = BT + 10 * (step + k)
            lines.append(f"put {metric} {ts} {value_text(rng)} {tags}")
    return ("\n".join(lines) + "\n").encode()


def stream(seed: int) -> list[bytes]:
    """The chunks of one case, in order: a first sight of 60 hosts, two
    steps on, a chunk in which 20 more hosts appear mid-stream, one with
    equal duplicates, a conflicting duplicate and an out-of-order
    timestamp, and a later hour."""
    rng = np.random.default_rng(seed)
    first = series_of(rng, 60)
    more = series_of(rng, 80)
    out = [chunk(rng, first, 0), chunk(rng, first, 3),
           chunk(rng, more, 6)]
    m, tags = first[0]
    m2, tags2 = first[1]
    m3, tags3 = first[2]
    out.append((
        f"put {m} {BT + 100} 5 {tags}\n"
        f"put {m2} {BT + 100} 7.5 {tags2}\n"
        f"put {m} {BT + 100} 5 {tags}\n"            # an equal duplicate
        f"put {m2} {BT + 100} 8.5 {tags2}\n"        # a conflicting one
        f"put {m3} {BT + 25} 1 {tags3}\n"           # behind its series
        f"put {m} {BT + 110} 6 {tags}\n").encode())
    out.append(chunk(rng, more, 400))               # the next hour
    return out


def open_db(path, shards: int = 1, **cfg) -> TSDB:
    os.makedirs(path, exist_ok=True)
    wal = os.path.join(path, "wal")
    conf = Config(**{"auto_create_metrics": True, "wal_path": wal,
                     "device_window_staging": 1 << 12, "port": 0,
                     "bind": "127.0.0.1", **cfg})
    store = (MemKVStore(wal_path=wal) if shards == 1 else
             ShardedKVStore(os.path.join(path, "sharded"), shards=shards))
    return TSDB(store, conf, start_compaction_thread=False)


def feed(db: TSDB, chunks, ingest, tenant: str = "default"):
    """Every chunk through ``ingest``; (points, error strings) a chunk."""
    return [ingest(db, wire.decode_puts(c, use_native=False),
                   tenant=tenant) for c in chunks]


def rows(store, table: str = "tsdb") -> list:
    return [(c.key, c.family, c.qualifier, c.value)
            for cells in store.scan(table, b"", b"\xff" * 64)
            for c in cells]


def window(db: TSDB) -> dict:
    """The device window after quiesce(): a metric's directory, whether
    it is dirty, and its chunks' valid columns end to end."""
    db.devwindow.quiesce()
    out = {}
    for uid, snap in db.devwindow._snapshot_metrics().items():
        cols = [np.concatenate([np.asarray(ch[c])[:ch["n"]]
                                for ch in snap["chunks"]] or [[]])
                for c in ("ts", "vals", "sid")]
        out[uid] = (snap["keys"], snap["dirty"], snap["epoch"],
                    [c.tolist() for c in cols])
    return out


def accounts(db: TSDB) -> dict:
    return {name: (st.points, st.count(), st.hh_series.to_json(),
                   st.hh_prefixes.to_json(), st.refused)
            for name, st in db.tenants._tenants.items()}


@pytest.fixture(scope="module", params=SEEDS)
def pair(request, tmp_path_factory):
    """The case's stream through the one-put path and through the plain
    reference, on two fresh stores."""
    chunks = stream(request.param)
    base = tmp_path_factory.mktemp("chunk")
    new = open_db(str(base / "new"))
    ref = open_db(str(base / "ref"))
    got = feed(new, chunks, wire.ingest_batch)
    want = feed(ref, chunks, ingest_batch_reference)
    yield new, ref, got, want, chunks
    new.shutdown()
    ref.shutdown()


def test_rows_equal_byte_for_byte(pair):
    new, ref, _g, _w, _c = pair
    assert rows(new.store) == rows(ref.store)
    assert rows(new.store, "tsdb-uid") == rows(ref.store, "tsdb-uid")
    assert len(rows(new.store)) > 600


def test_points_and_error_strings_equal_in_order(pair):
    _n, _r, got, want, _c = pair
    assert got == want
    # The fourth chunk: the conflicting duplicate fails its series alone.
    n, errs = got[3]
    assert n == 3 and len(errs) == 1 and "duplicate data" in errs[0]


def test_the_same_rows_are_queued_for_compaction(pair):
    new, ref, _g, _w, _c = pair
    assert dict(new.compactionq._queue) == dict(ref.compactionq._queue)
    assert new.compactionq._queue


def test_counters_and_tenant_accounts_equal(pair):
    new, ref, got, _w, _c = pair
    assert new.datapoints_added == ref.datapoints_added \
        == sum(n for n, _ in got)
    assert accounts(new) == accounts(ref)


def test_device_window_columns_equal_and_the_same_metrics_dirty(pair):
    new, ref, _g, _w, _c = pair
    a, b = window(new), window(ref)
    assert a == b
    # The out-of-order point dirtied its metric and no other.
    assert sorted(d for _k, d, _e, _c2 in a.values()) == [False] * 3 + [True]


def test_distinct_and_sketch_answers_equal(pair):
    new, ref, _g, _w, _c = pair
    targets = [f"/distinct?metric={m}&tagk={k}"
               for m in METRICS for k in ("host", "dc", "team")]
    targets += [f"/sketch?m={m}&q=p50,p90,p99" for m in METRICS]
    targets.append("/sketch?m=cpu.user{host=h7}&q=p50")
    a, b = serve(new, *targets), serve(ref, *targets)
    assert [s for s, _ in a] == [200] * len(targets)
    assert a == b
    assert json.loads(a[0][1])["distinct"] == pytest.approx(80, rel=0.05)


def test_a_chunk_is_one_wal_record_a_key_length(pair):
    new, ref, _g, _w, chunks = pair
    # The second chunk again, an hour on: known series, two key lengths.
    again = chunks[1].replace(str(BT // 1000).encode(),
                              str((BT + 7200) // 1000).encode())
    batch = wire.decode_puts(again, use_native=False)
    before = stat("wal.appends")
    n, errs = wire.ingest_batch(new, batch)
    assert (n, errs) == (len(batch.sid), [])
    assert stat("wal.appends") - before == 2
    before = stat("wal.appends")
    ingest_batch_reference(ref, batch)
    assert stat("wal.appends") - before == len(batch.series) == 240
    assert rows(new.store) == rows(ref.store)


def test_replayed_from_its_wal_the_store_is_equal_and_a_torn_chunk_absent(
        pair, tmp_path):
    new, _r, _g, _w, chunks = pair
    held = rows(new.store)
    wal = new.store._wal_path
    size = os.path.getsize(wal)
    last = chunk(np.random.default_rng(5), series_of(
        np.random.default_rng(6), 30), 800)
    n, errs = wire.ingest_batch(new, wire.decode_puts(last,
                                                      use_native=False))
    assert n and not errs

    def replayed(cut: int | None) -> list:
        copy = str(tmp_path / f"wal{cut}")
        shutil.copy(wal, copy)
        if cut is not None:
            os.truncate(copy, cut)
        s = MemKVStore(wal_path=copy, read_only=True)
        try:
            return rows(s)
        finally:
            s.close()

    assert replayed(None) == rows(new.store)
    # The chunk's points are in the WAL's last record (its two key
    # lengths make two; the torn one is the eight-tag series'): torn
    # anywhere, none of that record's rows is there, never a part.
    whole = replayed(None)
    end = os.path.getsize(wal)
    for cut in (end - 1, end - 300):
        part = replayed(cut)
        assert len(held) <= len(part) < len(whole)
        assert set(part) <= set(whole)
        lost = set(whole) - set(part)
        assert {len(k) for k, *_ in lost} == {max(len(k) for k, *_ in whole)}
    assert size < end


@pytest.mark.parametrize("seed", SEEDS)
def test_four_shards_equal_one(seed, tmp_path):
    chunks = stream(seed)
    one = open_db(str(tmp_path / "one"), device_window=False)
    four = open_db(str(tmp_path / "four"), shards=4, device_window=False)
    ref4 = open_db(str(tmp_path / "ref4"), shards=4, device_window=False)
    try:
        got1 = feed(one, chunks, wire.ingest_batch)
        got4 = feed(four, chunks, wire.ingest_batch)
        want4 = feed(ref4, chunks, ingest_batch_reference)
        assert got1 == got4 == want4
        assert rows(one.store) == rows(four.store) == rows(ref4.store)
        assert (dict(four.compactionq._queue)
                == dict(ref4.compactionq._queue))
    finally:
        for db in (one, four, ref4):
            db.shutdown()


def refusals(tmp_path, name, chunks, prepare=lambda db: None, **cfg):
    """The stream through both paths on stores opened with ``cfg``."""
    out = []
    for which, ingest in (("new", wire.ingest_batch),
                          ("ref", ingest_batch_reference)):
        db = open_db(str(tmp_path / f"{name}-{which}"),
                     device_window=False, **cfg)
        prepare(db)
        try:
            out.append((feed(db, chunks, ingest, tenant="acme"),
                        rows(db.store), db.datapoints_added,
                        accounts(db) if db.tenants else None))
        finally:
            db.shutdown()
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_an_unknown_metric_fails_alone_without_auto_metric(seed, tmp_path):
    def known(db):
        for m in METRICS[:3]:
            db.metrics.get_or_create_id(m)
    new, ref = refusals(tmp_path, "unknown", stream(seed)[:3], known,
                        auto_create_metrics=False)
    assert new == ref
    (n, errs), *_ = new[0]
    assert n and len(errs) == 60
    assert all(e.startswith("mem.free: ") and "No such name" in e
               for e in errs)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_tenant_over_its_series_limit_is_refused_series_by_series(
        seed, tmp_path):
    new, ref = refusals(tmp_path, "limit", stream(seed)[:3],
                        tenant_max_series=100)
    assert new == ref
    fed, _rows, added, acct = new
    assert all("[tenant-limit] " in e for _n, errs in fed for e in errs)
    assert [len(errs) for _n, errs in fed] == [140, 140, 220]
    assert added == sum(n for n, _ in fed) > 0
    assert acct["acme"][1] == 100 and acct["acme"][4] == 500


def test_a_fenced_writer_refuses_every_series_of_the_chunk(tmp_path):
    chunks = stream(SEEDS[0])[:2]
    out = []
    for which, ingest in (("new", wire.ingest_batch),
                          ("ref", ingest_batch_reference)):
        wal = str(tmp_path / which / "wal")
        os.makedirs(os.path.dirname(wal))
        ep = cepoch.epoch_path_for_wal(wal)
        cepoch.write_epoch(ep, 1, "w0")
        store = MemKVStore(wal_path=wal, writer_epoch=1,
                           epoch_guard=cepoch.EpochGuard(
                               ep, 1, interval_s=0.0))
        db = TSDB(store, Config(auto_create_metrics=True, wal_path=wal,
                                device_window=False),
                  start_compaction_thread=False)
        first = feed(db, chunks[:1], ingest)
        cepoch.bump_epoch(ep, "r0", expect=1)
        refused = [(n, [e.replace(wal, "<wal>") for e in errs])
                   for n, errs in feed(db, chunks[1:], ingest)]
        out.append((first, refused, rows(store), db.datapoints_added))
        store.close()
    assert out[0] == out[1]
    (n, errs), = out[0][1]
    assert n == 0 and len(errs) == 240
    assert all(": [fenced] " in e for e in errs)


def test_a_throttled_put_drops_every_window_and_queues_what_applied(
        tmp_path):
    db = open_db(str(tmp_path / "t"))
    try:
        first, second = stream(SEEDS[0])[:2]
        assert feed(db, [first], wire.ingest_batch)[0][1] == []
        db.store.throttle_rows = len(db.store._table("tsdb").rows) + 5
        (n, errs), = feed(db, [second.replace(
            str(BT // 1000).encode(), str((BT + 3600) // 1000).encode())],
            wire.ingest_batch)
        assert n == 0 and len(errs) == 240
        assert all("holds >=" in e for e in errs)
        assert len(db.compactionq._queue) == 0      # a new hour's rows
        assert all(dirty for _k, dirty, _e, _c in window(db).values())
        db.store.throttle_rows = None
    finally:
        db.shutdown()


@pytest.mark.parametrize("plan", ["resident", "raw", "disk-cache"])
def test_a_request_written_after_the_acknowledgement_holds_the_point(
        plan, tmp_path):
    cache = tmp_path / "qcache"
    cache.mkdir()
    db = open_db(str(tmp_path / "q"), cachedir=str(cache))
    tags = "host=h1 region=eu dc=d1"
    try:
        lines = [f"put cpu.user {BT + 10 * k} {k}.5 {tags}"
                 for k in range(30)]
        if plan == "raw":     # a chunk behind its series: a dirty metric
            lines.append(f"put cpu.user {BT + 50} 9 host=h2 dc=d1")
        wire.ingest_batch(db, wire.decode_puts(
            ("\n".join(lines) + "\n").encode(), use_native=False))
        if plan == "raw":
            wire.ingest_batch(db, wire.decode_puts(
                f"put cpu.user {BT + 40} 9 host=h2 dc=d1\n".encode(),
                use_native=False))
        for step in (30, 31):
            ts = BT + 10 * step
            n, errs = wire.ingest_batch(db, wire.decode_puts(
                f"put cpu.user {ts} 77.25 {tags}\n".encode(),
                use_native=False))
            assert (n, errs) == (1, [])
            # Acknowledged: the very next request ends at the point.
            target = q(BT, ts, "sum:10s-max:cpu.user{host=h1}",
                       trace=False)
            if plan == "disk-cache":
                target = target.replace("&nocache", "")
            for _again in range(2):
                (status, body), = serve(db, target)
                assert status == 200
                res, = json.loads(body)
                assert res["dps"][str(ts)] == 77.25
                assert len(res["dps"]) == step + 1
                assert res["rollup"] == ("raw" if plan == "raw"
                                         else "resident")
        if plan == "disk-cache":
            assert len(list(cache.glob("*.json"))) == 2
    finally:
        db.shutdown()


def test_a_second_full_size_fold_round_compiles_nothing():
    """A step of 40,000 one-point series, then what a snapshot's flush
    might find a while later (17,500 series, some with two points; a
    handful of tag values): the shapes of the first round are the
    second's."""
    livesketch._fold_tdigests.clear_cache()
    livesketch._fold_hlls.clear_cache()
    sk = LiveSketches(background=False, flush_points=1 << 30)
    rng = np.random.default_rng(3)
    keys = [b"\x00\x00\x01" + int(s).to_bytes(6, "big")
            for s in range(40000)]

    def uids(hosts):
        return [(b"\x00\x00\x01", b"\x00\x00\x01", int(h).to_bytes(3, "big"))
                for h in hosts]

    sk.observe_many(keys, np.arange(40000), rng.random(40000),
                    uids(range(4000)))
    sk.flush()
    compiled = (livesketch._fold_tdigests._cache_size(),
                livesketch._fold_hlls._cache_size())
    assert compiled == (2, 1)
    some = np.sort(rng.choice(40000, 17500, replace=False))
    of_point = np.sort(np.concatenate([np.arange(17500),
                                       rng.choice(17500, 900)]))
    sk.observe_many([keys[s] for s in some], of_point,
                    rng.random(len(of_point)), uids(range(40, 47)))
    sk.flush()
    sk.observe(keys[5], rng.random(3), uids([9]))
    sk.flush()
    assert (livesketch._fold_tdigests._cache_size(),
            livesketch._fold_hlls._cache_size()) == compiled
    assert sk.quantile(keys[:40000], 0.5)[0] == pytest.approx(0.5, abs=0.02)
    assert sk.distinct(b"\x00\x00\x01", b"\x00\x00\x01") == pytest.approx(
        4000, rel=0.05)


def test_chunks_from_many_threads_leave_what_one_thread_leaves(tmp_path):
    """More writers than cores on one TSDB, every writer's first chunk
    seeing series the others are resolving at that moment (the resolved
    series kept on the TSDB are shared): no point is lost, and the
    store is the one a single writer of the same chunks leaves."""
    import sys
    import threading

    workers = 2 * (os.cpu_count() or 4)
    shared = series_of(np.random.default_rng(1), 15)
    plans = []
    for w in range(workers):
        rng = np.random.default_rng(100 + w)
        own = [(m, f"host=w{w}h{h} dc=d{h % 3}") for h in range(10)
               for m in METRICS]
        # Its own hosts, and the shared series at timestamps of its own.
        plans.append([chunk(rng, own, 0),
                      chunk(rng, shared, 10 * w),
                      chunk(rng, own + shared, 400 + 10 * w)])
    many = open_db(str(tmp_path / "many"))
    one = open_db(str(tmp_path / "one"))
    done: list[int] = []

    def write(plan):
        done.append(sum(n for n, errs in feed(many, plan, wire.ingest_batch)
                        if not errs))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write, args=(p,)) for p in plans]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    try:
        want = sum(n for p in plans
                   for n, _ in feed(one, p, ingest_batch_reference))
        assert sum(done) == want == many.datapoints_added
        # UIDs are handed out in arrival order, so rows are compared by
        # what they say, not by their bytes.
        def said(db):
            return sorted(
                (db.metrics.get_name(k[:3]), c.timestamps.tolist(),
                 c.values.tolist())
                for k, c in db.scan_columns(b"", b"\xff" * 64))
        assert said(many) == said(one)
        assert accounts(many)["default"][:2] == accounts(one)["default"][:2]
    finally:
        many.shutdown()
        one.shutdown()


def test_a_booted_store_compiles_its_folds_on_its_first_batch(tmp_path):
    """A store loaded from a snapshot (a daemon's boot) runs the fold
    shapes of its first batch's width class before that batch returns:
    a deployment's warm-up pays the compile, not a request beside some
    later fold on the folder thread. A fresh store (the builder's)
    does not."""
    keys = [b"\x00\x00\x02" + int(s).to_bytes(6, "big") for s in range(300)]
    tag = [(b"\x00\x00\x02", b"\x00\x00\x01", b"\x00\x00\x07")]
    fresh = LiveSketches(flush_points=1 << 20)
    fresh.observe_many(keys, np.arange(300), np.ones(300), tag)
    assert not fresh._td_warm
    path = str(tmp_path / "sketches")
    fresh.save(path)
    booted = LiveSketches.load(path, flush_points=1 << 20)
    livesketch._fold_tdigests.clear_cache()
    livesketch._fold_hlls.clear_cache()
    booted.observe_many(keys[:5], np.arange(5), np.ones(5), ())
    assert booted._td_warm == {(512, 8)} and booted._buffered == 5
    compiled = (livesketch._fold_tdigests._cache_size(),
                livesketch._fold_hlls._cache_size())
    assert compiled == (2, 1)
    booted.observe_many(keys, np.arange(300), np.ones(300), tag)
    booted.flush()
    assert (livesketch._fold_tdigests._cache_size(),
            livesketch._fold_hlls._cache_size()) == compiled
    assert booted.quantile(keys, 0.5)[0] == 1.0


@pytest.mark.parametrize("auto_metric", [True, False])
def test_dropcaches_forgets_a_renamed_series(auto_metric, tmp_path):
    """``uid rename`` + ``dropcaches``: a series resolved before the
    rename must not keep writing under the UID its old name had. With
    ``--auto-metric`` the old name gets a UID of its own; without, it is
    unknown."""
    db = open_db(str(tmp_path), auto_create_metrics=auto_metric)
    try:
        for name in ("m.old", "m.other"):
            db.metrics.get_or_create_id(name)
        tags = "host=h1 dc=d1"
        put = lambda m, t: wire.ingest_batch(db, wire.decode_puts(  # noqa
            f"put {m} {BT + t} {t} {tags}\n".encode(), use_native=False))
        assert put("m.old", 10) == (1, [])
        old_uid = db.metrics.get_id("m.old")
        db.metrics.rename("m.old", "m.new")
        db.drop_caches()
        n, errs = put("m.old", 20)
        if auto_metric:
            assert (n, errs) == (1, [])
            assert db.metrics.get_id("m.old") != old_uid
        else:
            assert n == 0 and len(errs) == 1 and "m.old" in errs[0]
        assert put("m.new", 30) == (1, [])
        assert put("m.other", 40) == (1, [])
        by_uid = {}
        for key, cols in db.scan_columns(b"", b"\xff" * 64):
            by_uid.setdefault(key[:3], []).extend(
                (cols.timestamps - BT).tolist())
        want = {old_uid: [10, 30], db.metrics.get_id("m.other"): [40]}
        if auto_metric:
            want[db.metrics.get_id("m.old")] = [20]
        assert by_uid == want
    finally:
        db.shutdown()
