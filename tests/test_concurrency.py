"""Race-detection stress tests (SURVEY §5.2): hammer the storage
engine's documented thread contracts from many threads at once and
check the invariants that the lock discipline is supposed to enforce.

The reference ships no sanitizer pass either (its thread-safety is
javadoc contracts, e.g. CompactionQueue's synchronized maps); this
module is the analog of a race detector for the contracts this build
actually relies on in production:
  - put_many/put_many_columnar vs checkpoint() (the overlapped-spill
    design: freeze/swap under brief locks, phase-2 write outside),
  - scans concurrent with spills (snapshot semantics, no torn rows),
  - atomic_increment / compare_and_set linearizability,
  - UniqueId get_or_create races (reverse-then-forward CAS, losers
    must converge on the winner's id).

Failures here are flaky by nature — any assertion tripping means a
real race, not a bad test seed.
"""

import struct
import threading
import time

import numpy as np
import pytest

from opentsdb_tpu.storage.kv import Cell, MemKVStore

T = "tsdb"
F = b"t"


def run_threads(fns):
    errs = []

    def wrap(fn):
        try:
            fn()
        except BaseException as e:  # pragma: no cover - only on a race
            errs.append(e)

    ts = [threading.Thread(target=wrap, args=(fn,)) for fn in fns]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
        assert not t.is_alive(), "worker deadlocked"
    if errs:
        raise errs[0]


class TestIngestVsCheckpoint:
    def test_concurrent_put_many_and_checkpoints(self, tmp_path):
        """4 writer threads + a checkpoint loop: every acknowledged
        cell must be readable afterwards, across however many
        generations the spills produced, and again after reopen."""
        store = MemKVStore(wal_path=str(tmp_path / "wal"))
        writers, per = 4, 300
        done = threading.Event()

        def writer(w):
            def fn():
                for i in range(per):
                    cells = [(b"w%d-k%04d" % (w, i), b"q%d" % j,
                              b"v%d-%d-%d" % (w, i, j))
                             for j in range(3)]
                    store.put_many(T, F, cells)
            return fn

        def ckpt():
            while not done.is_set():
                store.checkpoint()
            store.checkpoint()

        ck = threading.Thread(target=ckpt)
        ck.start()
        try:
            run_threads([writer(w) for w in range(writers)])
        finally:
            done.set()
            ck.join(timeout=120)
        assert not ck.is_alive()

        def check(s):
            for w in range(writers):
                for i in range(per):
                    cells = s.get(T, b"w%d-k%04d" % (w, i))
                    assert [c.value for c in cells] == [
                        b"v%d-%d-%d" % (w, i, j) for j in range(3)], \
                        (w, i, cells)

        check(store)
        store.close()
        again = MemKVStore(wal_path=str(tmp_path / "wal"))
        check(again)
        again.close()

    def test_scans_during_spills_see_whole_rows(self, tmp_path):
        """Scans racing ingest + checkpoints may miss rows written
        after their snapshot, but every row they DO yield must be
        internally complete (all 3 cells) — a torn row means a reader
        observed mid-merge state."""
        store = MemKVStore(wal_path=str(tmp_path / "wal"))
        done = threading.Event()

        def writer():
            for i in range(800):
                store.put_many(T, F, [
                    (b"s-%05d" % i, b"q%d" % j, b"x" * 8)
                    for j in range(3)])
            done.set()

        def ckpt():
            while not done.is_set():
                store.checkpoint()

        def scanner():
            while not done.is_set():
                for key, items in store.scan_raw(T, b"s-", b"s-\xff"):
                    assert len(items) == 3, (key, items)

        run_threads([writer, ckpt, scanner, scanner])
        store.close()

    def test_deletes_vs_checkpoint_tombstones(self, tmp_path):
        """Interleaved delete_row + checkpoint: a row deleted after
        the spill snapshot must stay dead (tombstones over whichever
        generation holds it), never resurrect."""
        store = MemKVStore(wal_path=str(tmp_path / "wal"))
        n = 400
        for i in range(n):
            store.put(T, b"d-%04d" % i, F, b"q", b"v")
        done = threading.Event()

        def deleter():
            for i in range(n):
                store.delete_row(T, b"d-%04d" % i)
            done.set()

        def ckpt():
            while not done.is_set():
                store.checkpoint()
            store.checkpoint()

        run_threads([deleter, ckpt])
        for i in range(n):
            assert store.get(T, b"d-%04d" % i) == [], i
        store.close()
        again = MemKVStore(wal_path=str(tmp_path / "wal"))
        for i in range(n):
            assert again.get(T, b"d-%04d" % i) == [], i
        again.close()


class TestAtomics:
    def test_atomic_increment_linearizable(self):
        store = MemKVStore()
        per, threads = 500, 8

        def inc():
            for _ in range(per):
                store.atomic_increment(T, b"ctr", F, b"q")

        run_threads([inc] * threads)
        raw = store.get(T, b"ctr")[0].value
        assert struct.unpack(">q", raw)[0] == per * threads

    def test_cas_exactly_one_winner(self):
        store = MemKVStore()
        wins = []

        def racer(i):
            def fn():
                if store.compare_and_set(T, b"cas", F, b"q", None,
                                         b"w%d" % i):
                    wins.append(i)
            return fn

        run_threads([racer(i) for i in range(16)])
        assert len(wins) == 1
        assert store.get(T, b"cas") == [
            Cell(b"cas", F, b"q", b"w%d" % wins[0])]


class TestUidRaces:
    def test_get_or_create_converges_under_race(self):
        """16 threads racing get_or_create over a shared name set must
        agree on one id per name, ids must be unique, and the reverse
        map must match (reference UniqueId race-loser retry,
        UniqueId.java:297-326)."""
        from opentsdb_tpu.uid.uniqueid import UniqueId

        store = MemKVStore()
        store.ensure_table("tsdb-uid")
        names = [f"metric.{i}" for i in range(40)]
        results: dict[int, dict[str, bytes]] = {}

        def worker(w):
            def fn():
                uid = UniqueId(store, "tsdb-uid", "metrics", 3)
                got = {}
                for name in names:
                    got[name] = uid.get_or_create_id(name)
                results[w] = got
            return fn

        run_threads([worker(w) for w in range(16)])
        base = results[0]
        assert len(set(base.values())) == len(names), "duplicate ids"
        for w, got in results.items():
            assert got == base, f"worker {w} disagrees"
        fresh = UniqueId(store, "tsdb-uid", "metrics", 3)
        for name in names:
            assert fresh.get_name(base[name]) == name


class TestServerConcurrentIngestQuery:
    def test_add_batch_vs_executor_run(self):
        """TSDB.add_batch from 2 threads while an executor queries the
        same metric: queries must never error or return torn buckets
        (each returned value must be one of the written values)."""
        from opentsdb_tpu.core.tsdb import TSDB
        from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
        from opentsdb_tpu.utils.config import Config

        BT = 1356998400
        cfg = Config(auto_create_metrics=True, enable_sketches=False)
        cfg.device_window = False
        tsdb = TSDB(MemKVStore(), cfg, start_compaction_thread=False)
        tsdb.metrics.get_or_create_id("c.m")  # reader may win the race
        ex = QueryExecutor(tsdb, backend="cpu")
        done = threading.Event()

        def writer(w):
            def fn():
                ts = BT + np.arange(300) * 10
                for i in range(30):
                    tsdb.add_batch("c.m", ts + i,
                                   np.full(300, 5.0),
                                   {"host": f"w{w}", "run": f"r{i}"})
            return fn

        def reader():
            spec = QuerySpec("c.m", {}, "max")
            while not done.is_set():
                for r in ex.run(spec, BT, BT + 4000):
                    vals = np.asarray(r.values)
                    assert np.all(vals == 5.0), vals[vals != 5.0]

        t = threading.Thread(target=reader)
        t.start()
        try:
            run_threads([writer(w) for w in range(2)])
        finally:
            done.set()
            t.join(timeout=120)
        assert not t.is_alive()


class TestReplicaRacesWriter:
    def test_replica_refresh_races_writer_checkpoints(self, tmp_path):
        """A replica polls refresh() while the writer ingests and
        checkpoints (rotations, spills, tiered merges) at full speed.
        Every replica read must be a consistent prefix of the writer's
        history: for monotone per-key versions, a key's value may lag
        but never go backwards and never tear."""
        wal = str(tmp_path / "wal")
        writer = MemKVStore(wal_path=wal)
        # tight cap => frequent merges while the replica polls
        writer._MAX_GENERATIONS = 3
        stop = threading.Event()
        versions = {b"k%02d" % i: 0 for i in range(20)}
        errs: list[BaseException] = []

        def write_loop():
            v = 0
            while not stop.is_set():
                v += 1
                for k in versions:
                    writer.put(T, k, F, b"q", b"%06d" % v)
                    versions[k] = v
                if v % 3 == 0:
                    writer.checkpoint()

        def replica_loop():
            replica = MemKVStore(wal_path=wal, read_only=True)
            try:
                last_seen = {k: 0 for k in versions}
                while not stop.is_set():
                    replica.refresh()
                    for k in list(last_seen):
                        cells = replica.get(T, k)
                        if not cells:
                            continue
                        v = int(cells[0].value)
                        assert v >= last_seen[k], \
                            f"{k} went backwards: {last_seen[k]}->{v}"
                        last_seen[k] = v
            finally:
                replica.close()

        def guard(fn):
            def wrapped():
                try:
                    fn()
                except BaseException as e:
                    errs.append(e)
            return wrapped

        threads = [threading.Thread(target=guard(write_loop))] + [
            threading.Thread(target=guard(replica_loop))
            for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(6)
        stop.set()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "deadlock"
        if errs:
            raise errs[0]
        writer.close()
        # A fresh replica sees the final state exactly.
        final = MemKVStore(wal_path=wal, read_only=True)
        for k, v in versions.items():
            got = int(final.get(T, k)[0].value)
            assert got == v, (k, got, v)
        final.close()

    @pytest.mark.parametrize("moved", ["rotation", "commit"])
    def test_replica_load_beside_a_checkpoint_starts_over(self, tmp_path,
                                                          moved):
        """The race behind 'went backwards', made to happen: the writer
        rotates its WAL (or commits a whole checkpoint) after the
        replica's load has looked for <wal>.old and before it replays
        the WAL. The load must notice and start over, not serve the
        older generation without the records that moved."""
        from opentsdb_tpu.fault import faultpoints as fp
        wal = str(tmp_path / "wal")
        writer = MemKVStore(wal_path=wal)
        writer.put(T, b"k", F, b"q", b"000001")
        assert writer.checkpoint() == 1
        writer.put(T, b"k", F, b"q", b"000002")
        replica = MemKVStore(wal_path=wal, read_only=True)
        assert int(replica.get(T, b"k")[0].value) == 2
        replay, fired = replica._replay, []

        def racing(path, start=0):
            if not path.endswith(".old") and not fired:
                fired.append(path)
                if moved == "rotation":
                    fp.arm("kv.checkpoint.freeze", "raise")
                    try:
                        with pytest.raises(fp.FaultInjected):
                            writer.checkpoint()
                    finally:
                        fp.clear()
                else:
                    assert writer.checkpoint() == 1
            return replay(path, start)

        replica._replay = racing
        try:
            with replica._lock:
                replica._rebuild_locked()
            assert fired
            assert int(replica.get(T, b"k")[0].value) == 2
            # What the load recorded is what refresh() polls against:
            # nothing has moved since, so nothing is rebuilt again.
            rebuilds = replica.rebuilds
            assert replica.refresh() is False
            assert replica.rebuilds == rebuilds
        finally:
            replica.close()
            writer.close()
