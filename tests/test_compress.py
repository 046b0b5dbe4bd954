"""TSST4 compressed columnar blocks: codec round-trips, format v4
read/write/merge parity, golden query parity codec=none vs tsst4 at
shards 1 and 4 (live ingest, checkpoints, rollup stitching, replica
tailing), fsck block audits, /stats gauges, and the fused
decode-aggregate path's exact-or-fall-back contract."""

import os
import struct

import numpy as np
import pytest

from opentsdb_tpu.compress import codecs
from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.storage import sstable as sstable_mod
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.storage.sharded import ShardedKVStore
from opentsdb_tpu.storage.sstable import SSTable, merge_sstables, \
    write_sstable
from opentsdb_tpu.utils.config import Config

_U16 = struct.Struct(">H")
_U32 = struct.Struct(">I")


# ---------------------------------------------------------------------------
# raw-record builders (the v3 wire framing the codecs run over)
# ---------------------------------------------------------------------------

def frame(table: str, key: bytes, cells) -> bytes:
    tb = table.encode()
    parts = [_U16.pack(len(tb)), tb, _U16.pack(len(key)), key,
             _U32.pack(len(cells))]
    for fam, q, v in cells:
        parts += [_U16.pack(len(fam)), fam, _U16.pack(len(q)), q,
                  _U32.pack(len(v)), v]
    return b"".join(parts)


def float_cell(deltas, vals):
    q = b"".join(_U16.pack((d << 4) | 0xB) for d in deltas)
    v = np.asarray(vals, ">f4").tobytes()
    if len(deltas) > 1:
        v += b"\x00"
    return q, v


def int_cell(deltas, vals):
    qs, vs = [], []
    for d, x in zip(deltas, vals):
        for w, lo, hi in ((1, -2**7, 2**7 - 1), (2, -2**15, 2**15 - 1),
                          (4, -2**31, 2**31 - 1), (8, -2**63, 2**63 - 1)):
            if lo <= x <= hi:
                break
        qs.append(_U16.pack((d << 4) | (w - 1)))
        vs.append(int(x).to_bytes(w, "big", signed=True))
    v = b"".join(vs)
    if len(deltas) > 1:
        v += b"\x00"
    return b"".join(qs), v


def data_key(metric: int, base: int, tagv: int) -> bytes:
    return (metric.to_bytes(3, "big") + struct.pack(">I", base)
            + b"\x00\x00\x01" + tagv.to_bytes(3, "big"))


def build_run(rows):
    raw = b"".join(rows)
    offs = np.cumsum([0] + [len(r) for r in rows[:-1]])
    return raw, offs


class TestBlockCodecs:
    def test_float_block_round_trip(self):
        rng = np.random.default_rng(3)
        rows = []
        for r in range(120):
            n = int(rng.integers(1, 12))
            deltas = np.sort(rng.choice(3600, n, replace=False)).tolist()
            vals = np.cumsum(rng.normal(0, 1, n)) + 100
            rows.append(frame("tsdb", data_key(1, 1356998400 + r * 3600,
                                               (r % 9) + 1),
                              [(b"t",) + float_cell(deltas, vals)]))
        raw, offs = build_run(rows)
        tag, enc = codecs.encode_block(raw, offs)
        assert tag == codecs.TSF32
        assert len(enc) < len(raw)
        assert codecs.decode_block(tag, enc, len(raw)) == raw

    def test_int_block_round_trip_all_widths(self):
        rows = []
        vals_by_row = [[0], [127, -128], [200, -32768, 32767],
                       [2**31 - 1, -2**31, 5],
                       [2**62, -2**62, 1, -1]]
        for r, vals in enumerate(vals_by_row):
            deltas = list(range(0, 300 * len(vals), 300))
            rows.append(frame("tsdb", data_key(1, 1356998400 + r * 3600, 1),
                              [(b"t",) + int_cell(deltas, vals)]))
        raw, offs = build_run(rows)
        tag, enc = codecs.encode_block(raw, offs)
        assert tag == codecs.TSINT
        assert codecs.decode_block(tag, enc, len(raw)) == raw

    def test_foreign_rows_fall_back(self):
        # Multi-cell rows (uid-table shape) can't go columnar; zlib
        # picks them up when they deflate, verbatim otherwise.
        rows = [frame("tsdb-uid", b"name%03d" % i,
                      [(b"id", b"metrics", bytes([0, 0, i & 0xFF])),
                       (b"id", b"tagk", bytes([0, 1, i & 0xFF]))])
                for i in range(30)]
        raw, offs = build_run(rows)
        tag, enc = codecs.encode_block(raw, offs)
        assert tag in (codecs.ZLIB, codecs.VERBATIM)
        assert codecs.decode_block(tag, enc, len(raw)) == raw

    def test_incompressible_verbatim(self):
        raw = frame("x", os.urandom(16), [(b"f", os.urandom(64),
                                           os.urandom(512))])
        tag, enc = codecs.encode_block(raw, [0])
        assert codecs.decode_block(tag, enc, len(raw)) == raw

    def test_mixed_float_int_row_falls_back(self):
        q1, v1 = float_cell([100], [1.5])
        q2, v2 = int_cell([200], [42])
        rows = [frame("tsdb", data_key(1, 1356998400, 1),
                      [(b"t", q1 + q2, v1 + v2[:1] + b"\x00")])]
        raw, offs = build_run(rows)
        tag, enc = codecs.encode_block(raw, offs)
        # Either a structured codec proved an exact round-trip via the
        # self-check, or it fell back — decode must be exact always.
        assert codecs.decode_block(tag, enc, len(raw)) == raw

    def test_unknown_tag_and_size_mismatch_raise(self):
        raw = frame("tsdb", data_key(1, 1356998400, 1),
                    [(b"t",) + float_cell([5], [1.0])])
        tag, enc = codecs.encode_block(raw, [0])
        with pytest.raises(codecs.BlockCodecError):
            codecs.decode_block(99, enc, len(raw))
        with pytest.raises(codecs.BlockCodecError):
            codecs.decode_block(tag, enc, len(raw) + 1)

    def test_truncated_payload_raises(self):
        rng = np.random.default_rng(5)
        rows = [frame("tsdb", data_key(1, 1356998400 + r * 3600, 1),
                      [(b"t",) + float_cell(
                          list(range(0, 600, 60)),
                          rng.normal(100, 1, 10))])
                for r in range(10)]
        raw, offs = build_run(rows)
        tag, enc = codecs.encode_block(raw, offs)
        assert tag == codecs.TSF32
        with pytest.raises(codecs.BlockCodecError):
            codecs.decode_block(tag, enc[:len(enc) // 2], len(raw))


class TestSSTableV4:
    def _rows(self, seed=5, n=400):
        rng = np.random.default_rng(seed)
        rows = []
        for r in range(n):
            key = data_key(1, 1356998400 + (r // 4) * 3600, (r % 4) + 1)
            k = int(rng.integers(1, 9))
            deltas = np.sort(rng.choice(3600, k, replace=False)).tolist()
            if r % 3:
                cell = (b"t",) + float_cell(
                    deltas, np.cumsum(rng.normal(0, 1, k)) + 100)
            else:
                cell = (b"t",) + int_cell(
                    deltas, (rng.integers(0, 500, k)).tolist())
            rows.append(("tsdb", key, [cell]))
        uid = [("tsdb-uid", b"name%03d" % i,
                [(b"id", b"metrics", bytes([0, 0, i]))])
               for i in range(40)]
        return sorted(rows + uid, key=lambda r: (r[0], r[1]))

    def test_v4_parity_with_v3(self, tmp_path):
        rows = self._rows()
        p3, p4 = str(tmp_path / "g3"), str(tmp_path / "g4")
        assert write_sstable(p3, iter(rows)) \
            == write_sstable(p4, iter(rows), codec="tsst4")
        s3, s4 = SSTable(p3), SSTable(p4)
        assert (s3.format, s4.format) == (3, 4)
        assert s4.block_count > 0
        raw, enc = s4.codec_stats()
        assert raw > enc > 0
        for t in s3.tables():
            assert list(s3.iter_rows_range(t, b"", None)) \
                == list(s4.iter_rows_range(t, b"", None))
            k3, _ = s3._index[t]
            for k in k3[::7]:
                assert s3.get(t, k) == s4.get(t, k)
            ke3, st3, en3 = s3.record_extents(t)
            ke4, st4, en4 = s4.record_extents(t)
            assert ke3 == ke4
            assert np.array_equal(st3, st4)
            assert np.array_equal(en3, en4)
            b3, b4 = s3.bloom_bits(t), s4.bloom_bits(t)
            assert (b3 is None) == (b4 is None)
            if b3 is not None:
                assert np.array_equal(b3, b4)
        assert s4.block_audit() == 0
        s3.close()
        s4.close()

    @pytest.mark.parametrize("src_codec,out_codec", [
        ("none", "tsst4"), ("tsst4", "none"), ("tsst4", "tsst4")])
    def test_merge_re_encodes_across_formats(self, tmp_path, src_codec,
                                             out_codec):
        rows = self._rows(seed=9)
        psrc = str(tmp_path / "src")
        write_sstable(psrc, iter(rows),
                      codec=None if src_codec == "none" else src_codec)
        pref = str(tmp_path / "ref")
        write_sstable(pref, iter(rows))
        src, ref = SSTable(psrc), SSTable(pref)
        frozen = {"tsdb": ({rows[5][1]: {(b"t", b"\x01\x00"): b"\x07"}},
                           set(), False)}
        pm = str(tmp_path / "merged")
        merge_sstables(pm, [src], dict(frozen),
                       codec=None if out_codec == "none" else out_codec)
        pr = str(tmp_path / "merged_ref")
        merge_sstables(pr, [ref], dict(frozen))
        m, mr = SSTable(pm), SSTable(pr)
        assert m.format == (4 if out_codec == "tsst4" else 3)
        for t in mr.tables():
            assert list(m.iter_rows_range(t, b"", None)) \
                == list(mr.iter_rows_range(t, b"", None))
        for s in (src, ref, m, mr):
            s.close()

    def test_v1_v2_fixtures_still_serve_and_merge_into_v4(self, tmp_path):
        rows = self._rows(seed=13, n=60)
        old = sstable_mod.WRITE_FORMAT
        sstable_mod.WRITE_FORMAT = 2
        try:
            p2 = str(tmp_path / "g2")
            write_sstable(p2, iter(rows))
        finally:
            sstable_mod.WRITE_FORMAT = old
        s2 = SSTable(p2)
        assert s2.format == 2
        pm = str(tmp_path / "m4")
        merge_sstables(pm, [s2], {}, codec="tsst4")
        m = SSTable(pm)
        assert m.format == 4
        for t in s2.tables():
            assert list(m.iter_rows_range(t, b"", None)) \
                == list(s2.iter_rows_range(t, b"", None))
        s2.close()
        m.close()

    def test_block_audit_catches_corruption(self, tmp_path):
        rows = self._rows(seed=21)
        p4 = str(tmp_path / "g4")
        write_sstable(p4, iter(rows), codec="tsst4")
        s4 = SSTable(p4)
        # Flip a byte inside the first block's encoded payload.
        tag, raw_len, enc_len = s4.block_header(0)
        pos = s4._blk_file[0] + 9 + enc_len // 2
        s4.close()
        data = bytearray(open(p4, "rb").read())
        data[pos] ^= 0xFF
        open(p4, "wb").write(bytes(data))
        s4 = SSTable(p4)
        msgs = []
        assert s4.block_audit(msgs.append) >= 1
        assert msgs
        s4.close()


def _build_tsdb(tmp_path, codec, shards, name, rollups=False,
                sketches=False):
    d = str(tmp_path / name)
    os.makedirs(d, exist_ok=True)
    cfg = Config(auto_create_metrics=True, wal_path=d, shards=shards,
                 backend="cpu", enable_sketches=sketches,
                 device_window=False, sstable_codec=codec,
                 enable_rollups=rollups, rollup_catchup="sync")
    store = (ShardedKVStore(d, shards=shards) if shards > 1
             else MemKVStore(wal_path=os.path.join(d, "wal")))
    return TSDB(store, cfg, start_compaction_thread=False)


BASE = 1356998400


def _workload(t: TSDB, checkpoints=(1, 3)) -> None:
    rng = np.random.default_rng(11)
    for blk in range(5):
        for si in range(6):
            ts = BASE + blk * 4 * 3600 \
                + np.arange(0, 4 * 3600, 300, dtype=np.int64) + si
            vals = np.cumsum(rng.normal(0, 1, len(ts))) + 50 + si
            t.add_batch("m.cpu", ts, vals,
                        {"host": f"h{si}", "dc": "e" if si % 2 else "w"})
            iv = (np.arange(len(ts)) + si * 7).astype(np.int64)
            t.add_batch("m.int", ts, iv.astype(np.float64),
                        {"host": f"h{si}"},
                        is_float=np.zeros(len(ts), bool), int_values=iv)
        if blk in checkpoints:
            t.checkpoint()
    # Deletes + backfill exercise tombstone merges and overlay.
    key = t.row_key_for("m.cpu", {"host": "h3", "dc": "e"},
                        BASE + 3600, create_metric=False,
                        create_tags=False)
    t.store.delete_row(t.table, key)
    t.add_batch("m.cpu", np.array([BASE + 21 * 3600 + 5]),
                np.array([3.25]), {"host": "h1", "dc": "e"})
    t.checkpoint()


def _battery(t: TSDB, lo: int, hi: int):
    ex = QueryExecutor(t, backend="cpu")
    out = []
    for spec in [
            QuerySpec("m.cpu", {}, "sum", downsample=(3600, "avg")),
            QuerySpec("m.cpu", {"host": "*"}, "max",
                      downsample=(3600, "max")),
            QuerySpec("m.cpu", {"dc": "e"}, "p95",
                      downsample=(3600, "sum")),
            QuerySpec("m.int", {}, "sum", downsample=(3600, "sum")),
            QuerySpec("m.cpu", {}, "sum", rate=True),
            QuerySpec("m.cpu", {}, "zimsum", downsample=(7200, "count"))]:
        rs, plan, _ = ex.run_with_plan(spec, lo, hi)
        out.append((plan, [
            (tuple(sorted(r.tags.items())), r.timestamps.tobytes(),
             r.values.tobytes()) for r in rs]))
    if t.sketches is not None:
        out.append(("distinct",
                    ex.sketch_distinct("m.cpu", "host"),
                    ex.distinct_tagv("m.cpu", {}, "host", lo, hi)))
    return out


class TestGoldenParity:
    @pytest.mark.parametrize("shards", [1, 4])
    def test_codec_parity_battery(self, tmp_path, shards):
        """Every query answer byte-identical between codec=none and
        codec=tsst4 stores running the same workload — mid-ingest
        (live memtable over spilled tiers), post-checkpoint, with
        rollup stitching, and through a tailing replica."""
        lo, hi = BASE, BASE + 30 * 3600
        results = {}
        for codec in ("none", "tsst4"):
            t = _build_tsdb(tmp_path, codec, shards, f"s-{codec}",
                            rollups=True, sketches=True)
            try:
                rng = np.random.default_rng(11)
                got = []
                # Leg 1: live ingest (memtable + spilled generations).
                _workload(t)
                t.add_batch("m.cpu",
                            BASE + 22 * 3600
                            + np.arange(0, 1800, 300, dtype=np.int64),
                            np.cumsum(rng.normal(0, 1, 6)) + 9.0,
                            {"host": "h0", "dc": "w"})
                got.append(_battery(t, lo, hi))
                # Leg 2: everything frozen + rollup tier ready.
                t.checkpoint()
                if t.rollups is not None:
                    t.rollups.wait_ready()
                got.append(_battery(t, lo, hi))
                # Leg 3: replica over the same files.
                replica = (ShardedKVStore(t.store._dir, read_only=True)
                           if shards > 1 else
                           MemKVStore(wal_path=t.store._wal_path,
                                      read_only=True))
                try:
                    replica.refresh()
                    dump = []
                    for key, items in replica.scan_raw(
                            t.table, b"", b""):
                        dump.append((key, tuple(items)))
                    got.append(dump)
                finally:
                    replica.close()
                results[codec] = got
                if codec == "tsst4":
                    fmt = t.store.sstable_format_bytes()
                    assert set(fmt) == {4}
                    raw, enc = t.store.compress_stats()
                    assert raw > enc > 0
            finally:
                t.shutdown()
        assert results["none"] == results["tsst4"]

    def test_rollup_plans_serve_on_v4(self, tmp_path):
        t = _build_tsdb(tmp_path, "tsst4", 1, "roll", rollups=True)
        try:
            _workload(t)
            t.checkpoint()
            t.rollups.wait_ready()
            ex = QueryExecutor(t, backend="cpu")
            spec = QuerySpec("m.cpu", {}, "sum", downsample=(3600, "sum"))
            rs, plan, _ = ex.run_with_plan(spec, BASE, BASE + 30 * 3600)
            assert plan == "1h"
            saved, t.rollups = t.rollups, None
            try:
                raw = ex.run(spec, BASE, BASE + 30 * 3600)
            finally:
                t.rollups = saved
            assert len(rs) == len(raw)
            for a, b in zip(rs, raw):
                assert np.array_equal(a.timestamps, b.timestamps)
                assert np.array_equal(a.values, b.values)
        finally:
            t.shutdown()


class TestFsckAndStats:
    def test_fsck_clean_and_format_mix(self, tmp_path):
        from opentsdb_tpu.tools.fsck import run_fsck
        t = _build_tsdb(tmp_path, "tsst4", 1, "fsck")
        try:
            _workload(t)
            rep = run_fsck(t)
            assert rep.clean
            assert rep.format_counts.get(4, 0) >= 1
            assert rep.blocks >= 1
            assert rep.codec_errors == 0
        finally:
            t.shutdown()

    def test_fsck_counts_codec_errors(self, tmp_path):
        from opentsdb_tpu.tools.fsck import run_fsck
        t = _build_tsdb(tmp_path, "tsst4", 1, "fsckbad")
        try:
            _workload(t)
            sst = t.store._ssts[-1]
            # Corrupt the header's raw_len (byte 1): a size mismatch
            # is detected for every codec, including checksum-less
            # structured blocks.
            pos = sst._blk_file[0] + 1
            path = sst.path
            t.shutdown()
            data = bytearray(open(path, "rb").read())
            data[pos] ^= 0xFF
            open(path, "wb").write(bytes(data))
            t = _build_tsdb(tmp_path, "tsst4", 1, "fsckbad")
            rep = run_fsck(t)
            assert not rep.clean
            assert rep.codec_errors >= 1
        finally:
            t.shutdown()

    def test_cli_expect_clean_exit_codes(self, tmp_path):
        """`tsdb fsck --expect-clean` over a v4 store: 0 when clean,
        2 once a compressed block is corrupt (the crash-matrix / CI
        contract rides this exit code)."""
        from opentsdb_tpu.tools import cli
        t = _build_tsdb(tmp_path, "tsst4", 1, "clifsck")
        try:
            _workload(t)
            sst = t.store._ssts[-1]
            pos = sst._blk_file[0] + 1   # header raw_len byte
            path = sst.path
        finally:
            t.shutdown()
        wal = str(tmp_path / "clifsck" / "wal")
        assert cli.main(["fsck", "--wal", wal, "--backend", "cpu",
                         "--expect-clean"]) == 0
        data = bytearray(open(path, "rb").read())
        data[pos] ^= 0xFF
        open(path, "wb").write(bytes(data))
        assert cli.main(["fsck", "--wal", wal, "--backend", "cpu",
                         "--expect-clean"]) == 2

    def test_stats_gauges(self, tmp_path):
        from opentsdb_tpu.stats.collector import StatsCollector
        t = _build_tsdb(tmp_path, "tsst4", 1, "stats")
        try:
            _workload(t)
            c = StatsCollector("tsd")
            t.collect_stats(c)
            text = "\n".join(c.lines)
            assert "tsd.sstable.bytes" in text
            assert "format=v4" in text
            assert "tsd.compress.ratio" in text
            # The block decodes above landed compress.decode samples.
            from opentsdb_tpu.obs.registry import METRICS
            assert METRICS.timer("compress.decode").count > 0
        finally:
            t.shutdown()

    def test_block_faultpoint_raise_thaws(self, tmp_path):
        """An injected failure inside a compressed block write takes
        the spill-failure path: frozen tier thaws, store not wedged,
        a clean retry succeeds."""
        from opentsdb_tpu.fault import faultpoints
        t = _build_tsdb(tmp_path, "tsst4", 1, "fp")
        try:
            ts = BASE + np.arange(0, 6 * 3600, 300, dtype=np.int64)
            t.add_batch("m.cpu", ts, np.ones(len(ts)) + 0.5,
                        {"host": "h9"})
            faultpoints.arm("sst.write.block", "raise")
            try:
                with pytest.raises(faultpoints.FaultInjected):
                    t.checkpoint()
            finally:
                faultpoints.disarm("sst.write.block")
            assert t.checkpoint() > 0
            ex = QueryExecutor(t, backend="cpu")
            rs = ex.run(QuerySpec("m.cpu", {}, "sum",
                                  downsample=(3600, "sum")),
                        BASE, BASE + 30 * 3600)
            assert rs
        finally:
            t.shutdown()


class TestFusedPath:
    def _build(self, tmp_path, shards, name):
        d = str(tmp_path / name)
        os.makedirs(d, exist_ok=True)
        cfg = Config(auto_create_metrics=True, wal_path=d,
                     shards=shards, backend="tpu",
                     enable_sketches=False, device_window=False,
                     sstable_codec="tsst4")
        store = (ShardedKVStore(d, shards=shards) if shards > 1
                 else MemKVStore(wal_path=os.path.join(d, "wal")))
        t = TSDB(store, cfg, start_compaction_thread=False)
        rng = np.random.default_rng(11)
        for si in range(8):
            ts = BASE + np.arange(0, 24 * 3600, 300, dtype=np.int64) \
                + (si % 5)
            vals = np.cumsum(rng.normal(0, 1, len(ts))) + 50 + si
            t.add_batch("m.cpu", ts, vals,
                        {"host": f"h{si}", "dc": "e" if si % 2 else "w"})
        t.checkpoint()
        return t

    @pytest.mark.parametrize("shards", [1, 4])
    def test_fused_bit_identical_to_scan(self, tmp_path, shards):
        t = self._build(tmp_path, shards, f"f{shards}")
        try:
            ex = QueryExecutor(t, backend="tpu")
            for spec in [
                    QuerySpec("m.cpu", {}, "sum",
                              downsample=(3600, "avg")),
                    QuerySpec("m.cpu", {"host": "*"}, "max",
                              downsample=(3600, "max")),
                    QuerySpec("m.cpu", {"dc": "e"}, "sum",
                              downsample=(7200, "sum")),
                    QuerySpec("m.cpu", {}, "p95",
                              downsample=(3600, "sum")),
                    QuerySpec("m.cpu", {}, "sum",
                              downsample=(3600, "avg"), rate=True),
                    QuerySpec("m.cpu", {}, "zimsum",
                              downsample=(3600, "count"))]:
                r_f, plan_f, _ = ex.run_with_plan(
                    spec, BASE + 100, BASE + 20 * 3600)
                assert plan_f == "fused"
                t.config.sstable_fused_agg = False
                r_s, plan_s, _ = ex.run_with_plan(
                    spec, BASE + 100, BASE + 20 * 3600)
                t.config.sstable_fused_agg = True
                assert plan_s == "raw"
                assert len(r_f) == len(r_s)
                kf = {tuple(sorted(r.tags.items())): r for r in r_f}
                ks = {tuple(sorted(r.tags.items())): r for r in r_s}
                assert set(kf) == set(ks)
                for k in kf:
                    # The devwindow ("resident" plan) contract: the
                    # bucket grid is identical, values agree to f32
                    # tolerance (a different-but-exact execution plan
                    # may reassociate float32 group sums by an ulp).
                    assert np.array_equal(kf[k].timestamps,
                                          ks[k].timestamps)
                    np.testing.assert_allclose(
                        kf[k].values, ks[k].values,
                        rtol=1e-5, atol=1e-5)
        finally:
            t.shutdown()

    def test_fused_declines_dirty_and_mixed(self, tmp_path):
        t = self._build(tmp_path, 1, "fd")
        try:
            ex = QueryExecutor(t, backend="tpu")
            spec = QuerySpec("m.cpu", {}, "sum", downsample=(3600, "avg"))
            _, plan, _ = ex.run_with_plan(spec, BASE + 100,
                                          BASE + 20 * 3600)
            assert plan == "fused"
            # Live memtable point inside the range -> raw, same answer.
            t.add_batch("m.cpu", np.array([BASE + 3600 + 9]),
                        np.array([1.25]), {"host": "h0", "dc": "w"})
            r_raw, plan2, _ = ex.run_with_plan(spec, BASE + 100,
                                               BASE + 20 * 3600)
            assert plan2 == "raw"
            # Fused timer recorded the served query.
            from opentsdb_tpu.obs.registry import METRICS
            assert METRICS.timer("compress.fused_agg").count > 0
        finally:
            t.shutdown()

    def test_fused_serves_tsint_blocks_bit_identical(self, tmp_path):
        """Int-valued series spill as TSINT blocks and now SERVE the
        fused path (zigzag-delta inverse via one segmented int32
        cumsum) — answers must be bit-identical to a codec=none
        control store running the classic scan: integer decode is
        exact by the eligibility contract (every value fits int32),
        and the f32 cast matches the scan path's own kernel-entry
        cast."""
        import shutil as _sh
        specs = [QuerySpec("m.int", {}, "sum", downsample=(3600, "sum")),
                 QuerySpec("m.int", {"host": "*"}, "max",
                           downsample=(7200, "max")),
                 QuerySpec("m.int", {}, "p95", downsample=(3600, "avg"))]

        def build(name, codec):
            d = str(tmp_path / name)
            os.makedirs(d, exist_ok=True)
            cfg = Config(auto_create_metrics=True, wal_path=d,
                         shards=1, backend="tpu",
                         enable_sketches=False, device_window=False,
                         sstable_codec=codec)
            t = TSDB(MemKVStore(wal_path=os.path.join(d, "wal")), cfg,
                     start_compaction_thread=False)
            rng = np.random.default_rng(17)
            for si in range(4):
                ts = BASE + np.arange(0, 24 * 3600, 300,
                                      dtype=np.int64) + si
                vals = rng.integers(-1000, 10_000, len(ts))
                t.add_batch("m.int", ts, vals, {"host": f"h{si}"})
            t.checkpoint()
            return t

        t4 = build("ti4", "tsst4")
        t0 = build("ti0", "none")
        try:
            # The v4 store really holds TSINT blocks (not zlib/f32).
            from opentsdb_tpu.compress.codecs import TSINT
            sst = t4.store._ssts[-1]
            assert sst.format == 4
            tags = {sst.block_header(j)[0]
                    for j in range(sst.block_count)}
            assert TSINT in tags
            ex4 = QueryExecutor(t4, backend="tpu")
            ex0 = QueryExecutor(t0, backend="tpu")
            for spec in specs:
                r4, plan4, _ = ex4.run_with_plan(spec, BASE + 100,
                                                 BASE + 20 * 3600)
                assert plan4 == "fused", \
                    "TSINT blocks must serve the fused path"
                r0, plan0, _ = ex0.run_with_plan(spec, BASE + 100,
                                                 BASE + 20 * 3600)
                assert plan0 == "raw"
                assert len(r4) == len(r0)
                for a, b in zip(r4, r0):
                    assert a.tags == b.tags
                    assert np.array_equal(a.timestamps, b.timestamps)
                    # Bit-identical: exact int decode both sides.
                    assert np.array_equal(a.values, b.values)
        finally:
            t4.shutdown()
            t0.shutdown()
            _sh.rmtree(str(tmp_path / "ti4"), ignore_errors=True)

    def test_fused_declines_on_v3_store(self, tmp_path):
        d = str(tmp_path / "v3")
        os.makedirs(d, exist_ok=True)
        cfg = Config(auto_create_metrics=True, wal_path=d, shards=1,
                     backend="tpu", enable_sketches=False,
                     device_window=False)
        t = TSDB(MemKVStore(wal_path=os.path.join(d, "wal")), cfg,
                 start_compaction_thread=False)
        try:
            ts = BASE + np.arange(0, 6 * 3600, 300, dtype=np.int64)
            t.add_batch("m.cpu", ts, np.ones(len(ts)), {"host": "h0"})
            t.checkpoint()
            ex = QueryExecutor(t, backend="tpu")
            _, plan, _ = ex.run_with_plan(
                QuerySpec("m.cpu", {}, "sum", downsample=(3600, "avg")),
                BASE + 100, BASE + 5 * 3600)
            assert plan == "raw"
        finally:
            t.shutdown()


# ---------------------------------------------------------------------------
# Decline accounting: every remaining fused decline path must (a) fall
# back to an answer byte-identical to a codec=none control store and
# (b) bump a NAMED compress.fused.decline{reason=} counter — "zero
# undeclared declines" is the PR contract, and these pin each cause.
# ---------------------------------------------------------------------------

def _decline_count(reason: str) -> int:
    from opentsdb_tpu.obs.registry import METRICS
    return METRICS.counter("compress.fused.decline",
                           {"reason": reason}).value


def _mk_tpu_tsdb(tmp_path, name, codec):
    d = str(tmp_path / name)
    os.makedirs(d, exist_ok=True)
    cfg = Config(auto_create_metrics=True, wal_path=d, shards=1,
                 backend="tpu", enable_sketches=False,
                 device_window=False, sstable_codec=codec)
    return TSDB(MemKVStore(wal_path=os.path.join(d, "wal")), cfg,
                start_compaction_thread=False)


def _int_batch(t, metric, host, t0, span, step, seed, lo=-500, hi=5000):
    rng = np.random.default_rng(seed)
    ts = t0 + np.arange(0, span, step, dtype=np.int64)
    t.add_batch(metric, ts, rng.integers(lo, hi, len(ts)),
                {"host": host})


def _pair_answers(t4, t0, spec, lo, hi):
    """(rows, plan) from the tsst4 store and the codec=none control,
    with the control's plan asserted 'raw'."""
    ex4 = QueryExecutor(t4, backend="tpu")
    ex0 = QueryExecutor(t0, backend="tpu")
    r4, plan4, _ = ex4.run_with_plan(spec, lo, hi)
    r0, plan0, _ = ex0.run_with_plan(spec, lo, hi)
    assert plan0 == "raw"
    assert len(r4) == len(r0)
    for a, b in zip(r4, r0):
        assert a.tags == b.tags
        assert np.array_equal(a.timestamps, b.timestamps)
        assert np.array_equal(a.values, b.values)
    return plan4


class TestFusedDeclineCounters:
    SPEC = QuerySpec("m.d", {}, "sum", downsample=(3600, "sum"))

    def test_dirty_decline_counted_fallback_identical(self, tmp_path):
        t4 = _mk_tpu_tsdb(tmp_path, "dd4", "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "dd0", "none")
        try:
            for t in (t4, t0):
                _int_batch(t, "m.d", "a", BASE, 6 * 3600, 300, 5)
                t.checkpoint()
                # Live memtable point inside the range -> dirty.
                t.add_batch("m.d", np.array([BASE + 3600 + 7]),
                            np.array([11.0]), {"host": "a"})
            before = _decline_count("dirty")
            plan4 = _pair_answers(t4, t0, self.SPEC,
                                  BASE + 100, BASE + 5 * 3600)
            assert plan4 == "raw"
            assert _decline_count("dirty") >= before + 1
        finally:
            t4.shutdown()
            t0.shutdown()

    def test_mixed_codec_decline(self, tmp_path):
        """One generation spills TSINT blocks, the next TSF32 blocks
        for the same metric: one fused program cannot decode both, so
        the gather declines 'mixed-codec' and the scan serves."""
        t4 = _mk_tpu_tsdb(tmp_path, "mc4", "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "mc0", "none")
        try:
            for t in (t4, t0):
                _int_batch(t, "m.d", "a", BASE, 6 * 3600, 300, 6)
                t.checkpoint()
                rng = np.random.default_rng(7)
                ts = BASE + np.arange(0, 6 * 3600, 300,
                                      dtype=np.int64) + 3
                t.add_batch("m.d", ts,
                            np.cumsum(rng.normal(0, 1, len(ts))),
                            {"host": "b"})
                t.checkpoint()
            from opentsdb_tpu.compress.codecs import TSF32, TSINT
            tags = set()
            for sst in t4.store._ssts:
                tags |= {sst.block_header(j)[0]
                         for j in range(sst.block_count)}
            assert TSINT in tags and TSF32 in tags
            before = _decline_count("mixed-codec")
            plan4 = _pair_answers(t4, t0, self.SPEC,
                                  BASE + 100, BASE + 5 * 3600)
            assert plan4 == "raw"
            assert _decline_count("mixed-codec") >= before + 1
        finally:
            t4.shutdown()
            t0.shutdown()

    def test_duplicate_overlap_declines_disjoint_serves(self, tmp_path):
        """The same rowkey written across two generations: overlapping
        in-row time ranges decline (newest-wins overlay would need a
        host re-merge); DISJOINT ranges still serve fused — the lazy
        per-record delta-bounds check separates the two."""
        spec = self.SPEC
        # Overlapping: gen2 rewrites interleaved timestamps.
        t4 = _mk_tpu_tsdb(tmp_path, "do4", "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "do0", "none")
        try:
            for t in (t4, t0):
                _int_batch(t, "m.d", "a", BASE, 4 * 3600, 600, 8)
                t.checkpoint()
                _int_batch(t, "m.d", "a", BASE + 300, 4 * 3600, 600, 9)
                t.checkpoint()
            before = _decline_count("duplicate-overlap")
            plan4 = _pair_answers(t4, t0, spec,
                                  BASE + 100, BASE + 4 * 3600)
            assert plan4 == "raw"
            assert _decline_count("duplicate-overlap") >= before + 1
        finally:
            t4.shutdown()
            t0.shutdown()
        # Disjoint: gen1 holds each hour's first half, gen2 the rest.
        t4 = _mk_tpu_tsdb(tmp_path, "dj4", "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "dj0", "none")
        try:
            for t in (t4, t0):
                for h in range(4):
                    _int_batch(t, "m.d", "a", BASE + h * 3600, 1800,
                               300, 10 + h)
                t.checkpoint()
                for h in range(4):
                    _int_batch(t, "m.d", "a",
                               BASE + h * 3600 + 1800, 1800, 300,
                               20 + h)
                t.checkpoint()
            assert len(t4.store._ssts) >= 2
            plan4 = _pair_answers(t4, t0, spec,
                                  BASE + 100, BASE + 4 * 3600)
            assert plan4 == "fused"
        finally:
            t4.shutdown()
            t0.shutdown()

    @pytest.mark.parametrize("case", ["mixed-row", "wide-int"])
    def test_block_ineligible_decline(self, tmp_path, case):
        """A block the kernels cannot consume declines the whole gather
        'block-ineligible', and the scan serves: a row that holds
        integers and floats (its block falls to zlib, so it has no
        columns to decode), or integers whose deltas take more than
        the four payload bytes a point the device indexes."""
        t4 = _mk_tpu_tsdb(tmp_path, "bi4" + case, "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "bi0" + case, "none")
        try:
            for t in (t4, t0):
                _int_batch(t, "m.d", "a", BASE, 6 * 3600, 300, 5)
                ts = BASE + np.arange(0, 6 * 3600, 300, dtype=np.int64)
                if case == "mixed-row":
                    t.add_batch("m.d", ts + 7, np.random.default_rng(
                        1).normal(size=len(ts)), {"host": "a"})
                else:
                    t.add_batch("m.d", ts, 2**40 + np.arange(len(ts)),
                                {"host": "b"})
                t.checkpoint()
            from opentsdb_tpu.compress.codecs import TSINT, ZLIB
            sst = t4.store._ssts[-1]
            tags = {sst.block_header(j)[0]
                    for j in range(sst.block_count)}
            assert (ZLIB if case == "mixed-row" else TSINT) in tags
            before = _decline_count("block-ineligible")
            plan4 = _pair_answers(t4, t0, self.SPEC,
                                  BASE + 100, BASE + 5 * 3600)
            assert plan4 == "raw"
            assert _decline_count("block-ineligible") == before + 1
        finally:
            t4.shutdown()
            t0.shutdown()

    def test_mesh_indivisible_counted_still_serves(self, tmp_path):
        """A mesh whose device count does not divide the padded point
        grid declines the SHARDED leg (counted) but still serves the
        query fused on one device — same plan, same answer."""
        import types
        t4 = _mk_tpu_tsdb(tmp_path, "mi4", "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "mi0", "none")
        try:
            for t in (t4, t0):
                _int_batch(t, "m.d", "a", BASE, 6 * 3600, 300, 12)
                _int_batch(t, "m.d", "b", BASE, 6 * 3600, 300, 13)
                t.checkpoint()
            ex = QueryExecutor(t4, backend="tpu")
            # Three devices never divide a pow2-padded point count.
            ex.fused.mesh = types.SimpleNamespace(devices=np.zeros(3))
            before = _decline_count("mesh-indivisible")
            r_m, plan_m, _ = ex.run_with_plan(self.SPEC, BASE + 100,
                                              BASE + 5 * 3600)
            assert plan_m == "fused"
            assert _decline_count("mesh-indivisible") >= before + 1
            plan4 = _pair_answers(t4, t0, self.SPEC,
                                  BASE + 100, BASE + 5 * 3600)
            assert plan4 == "fused"
            ex0 = QueryExecutor(t0, backend="tpu")
            r0, _, _ = ex0.run_with_plan(self.SPEC, BASE + 100,
                                         BASE + 5 * 3600)
            for a, b in zip(r_m, r0):
                assert np.array_equal(a.values, b.values)
        finally:
            t4.shutdown()
            t0.shutdown()


class TestDeviceBlockCache:
    def test_hit_miss_counters_and_repeat_identity(self, tmp_path):
        """First fused query decodes every covering block on device
        (misses); a second query over the same blocks re-serves from
        the cache (hits, zero new misses) with identical answers."""
        from opentsdb_tpu.obs.registry import METRICS
        hit = METRICS.counter("compress.devcache.hit")
        miss = METRICS.counter("compress.devcache.miss")
        t4 = _mk_tpu_tsdb(tmp_path, "dc4", "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "dc0", "none")
        try:
            for t in (t4, t0):
                for si in range(4):
                    _int_batch(t, "m.d", f"h{si}", BASE, 24 * 3600,
                               300, 30 + si)
                t.checkpoint()
            ex4 = QueryExecutor(t4, backend="tpu")
            assert ex4.fused.devcache is not None
            ex0 = QueryExecutor(t0, backend="tpu")
            spec = QuerySpec("m.d", {}, "sum", downsample=(3600, "sum"))
            h0, m0 = hit.value, miss.value
            r1, plan1, _ = ex4.run_with_plan(spec, BASE + 100,
                                             BASE + 20 * 3600)
            assert plan1 == "fused"
            assert miss.value > m0
            m1 = miss.value
            assert len(ex4.fused.devcache) > 0
            # A different window over the same blocks: the stage cache
            # misses but every block decode is already resident.
            spec2 = QuerySpec("m.d", {}, "max", downsample=(7200, "max"))
            r2, plan2, _ = ex4.run_with_plan(spec2, BASE + 50,
                                             BASE + 18 * 3600)
            assert plan2 == "fused"
            assert hit.value > h0
            assert miss.value == m1
            for spec_i, lo, hi, rows in [
                    (spec, BASE + 100, BASE + 20 * 3600, r1),
                    (spec2, BASE + 50, BASE + 18 * 3600, r2)]:
                r0, plan0, _ = ex0.run_with_plan(spec_i, lo, hi)
                assert plan0 == "raw"
                assert len(rows) == len(r0)
                for a, b in zip(rows, r0):
                    assert np.array_equal(a.timestamps, b.timestamps)
                    assert np.array_equal(a.values, b.values)
        finally:
            t4.shutdown()
            t0.shutdown()

    def test_selector_compaction_bit_identical(self, tmp_path):
        """A literal tag filter that drops most records runs the
        selective stage (slab_stage_sel): the blocks decoded whole into
        the cache, only the matching points gathered, stage cost
        proportional to the match. Answers stay bit-identical to the
        codec=none scan; with the cache off the plan declines
        (``cache-off``) and the raw plan gives the same answers."""
        t4 = _mk_tpu_tsdb(tmp_path, "sc4", "tsst4")
        t0 = _mk_tpu_tsdb(tmp_path, "sc0", "none")
        try:
            for t in (t4, t0):
                rng = np.random.default_rng(41)
                for si in range(8):
                    ts = BASE + np.arange(0, 24 * 3600, 300,
                                          dtype=np.int64) + si
                    t.add_batch("m.d", ts,
                                rng.integers(-500, 5000, len(ts)),
                                {"host": f"h{si}", "dc": f"d{si % 4}"})
                t.checkpoint()
            ex4 = QueryExecutor(t4, backend="tpu")
            ex0 = QueryExecutor(t0, backend="tpu")
            specs = [
                # 2 of 8 series match: selective, aggregated.
                QuerySpec("m.d", {"dc": "d1"}, "sum",
                          downsample=(3600, "sum")),
                # Group-by over a selective subset.
                QuerySpec("m.d", {"host": "h2", "dc": "*"}, "max",
                          downsample=(7200, "max"))]
            for cache, plan in (("on", "fused"), ("off", "raw")):
                if cache == "off":
                    ex4.fused.devcache = None
                ex4._frag_cache.clear()
                ex4.fused.stage_cache.clear()
                declined = _decline_count("cache-off")
                for spec in specs:
                    r4, plan4, _ = ex4.run_with_plan(
                        spec, BASE + 100, BASE + 20 * 3600)
                    assert plan4 == plan, (cache, spec.tags)
                    r0, plan0, _ = ex0.run_with_plan(
                        spec, BASE + 100, BASE + 20 * 3600)
                    assert plan0 == "raw"
                    assert len(r4) == len(r0) > 0
                    for a, b in zip(r4, r0):
                        assert a.tags == b.tags
                        assert np.array_equal(a.timestamps,
                                              b.timestamps)
                        assert np.array_equal(a.values, b.values)
                assert _decline_count("cache-off") - declined == (
                    len(specs) if cache == "off" else 0)
        finally:
            t4.shutdown()
            t0.shutdown()


class TestRollsumPath:
    """ROLLSUM: the structured rollup-record codec. Coverage contract:
    tier spills carry ROLLSUM-tagged blocks, rollup-served answers are
    byte-for-byte identical to a codec=none control, the tier's
    block-direct read path engages, fsck audits the blocks (per-codec
    counts included), and a corrupted ROLLSUM block fails
    ``fsck --expect-clean`` with exit 2."""

    def _build(self, tmp_path, name, codec):
        d = str(tmp_path / name)
        os.makedirs(d, exist_ok=True)
        cfg = Config(auto_create_metrics=True, wal_path=d, shards=1,
                     backend="cpu", enable_sketches=False,
                     device_window=False, sstable_codec=codec,
                     enable_rollups=True, rollup_catchup="sync")
        t = TSDB(MemKVStore(wal_path=os.path.join(d, "wal")), cfg,
                 start_compaction_thread=False)
        rng = np.random.default_rng(7)
        for si in range(3):
            ts = BASE + np.arange(0, 35 * 86400, 3600,
                                  dtype=np.int64) + si
            t.add_batch("m.cpu", ts, rng.normal(size=len(ts)),
                        {"host": f"h{si}"})
        t.checkpoint()
        return t

    @staticmethod
    def _tier_tags(t):
        from opentsdb_tpu.compress.codecs import CODEC_NAMES
        tags = {}
        for res, stores in t.rollups.stores.items():
            for s in stores:
                for sst in getattr(s, "_ssts", []):
                    for j in range(sst.block_count):
                        nm = CODEC_NAMES.get(sst.block_header(j)[0])
                        tags[nm] = tags.get(nm, 0) + 1
        return tags

    def test_rollsum_blocks_serve_byte_identical(self, tmp_path):
        t4 = self._build(tmp_path, "rs4", "tsst4")
        t0 = self._build(tmp_path, "rs0", "none")
        try:
            assert self._tier_tags(t4).get("rollsum", 0) >= 1
            ex4 = QueryExecutor(t4, backend="cpu")
            ex0 = QueryExecutor(t0, backend="cpu")
            spec = QuerySpec("m.cpu", {}, "sum",
                             downsample=(86400, "avg"))
            r4, p4, _ = ex4.run_with_plan(spec, BASE,
                                          BASE + 30 * 86400)
            r0, p0, _ = ex0.run_with_plan(spec, BASE,
                                          BASE + 30 * 86400)
            assert p4 == "1d" and p0 == "1d"
            assert len(r4) == len(r0) > 0
            for a, b in zip(r4, r0):
                assert np.array_equal(a.timestamps, b.timestamps)
                assert np.array_equal(a.values, b.values)
            # The tier's block-direct read engaged (parsed ROLLSUM
            # columns cached on the sstable, no per-row re-framing).
            assert any(
                sst.__dict__.get("_rollsum_cache")
                for stores in t4.rollups.stores.values()
                for s in stores for sst in getattr(s, "_ssts", []))
        finally:
            t4.shutdown()
            t0.shutdown()

    def test_fsck_audits_rollsum_and_codec_counts(self, tmp_path):
        from opentsdb_tpu.tools.fsck import run_fsck
        t = self._build(tmp_path, "rsf", "tsst4")
        try:
            rep = run_fsck(t)
            assert rep.clean
            assert rep.codec_counts.get("rollsum", 0) >= 1
            # Data-table blocks are counted per codec too.
            assert sum(rep.codec_counts.values()) == rep.blocks
        finally:
            t.shutdown()

    def test_cli_expect_clean_on_corrupt_rollsum(self, tmp_path):
        from opentsdb_tpu.compress.codecs import ROLLSUM
        from opentsdb_tpu.tools import cli
        t = self._build(tmp_path, "rsc", "tsst4")
        try:
            path = pos = None
            for stores in t.rollups.stores.values():
                for s in stores:
                    for sst in getattr(s, "_ssts", []):
                        for j in range(sst.block_count):
                            tag, _, enc_len = sst.block_header(j)
                            if tag == ROLLSUM:
                                path = sst.path
                                pos = sst._blk_file[j] + 9 \
                                    + enc_len // 2
                                break
                        if path:
                            break
                    if path:
                        break
                if path:
                    break
            assert path is not None
        finally:
            t.shutdown()
        wal = str(tmp_path / "rsc" / "wal")
        assert cli.main(["fsck", "--wal", wal, "--backend", "cpu",
                         "--expect-clean"]) == 0
        data = bytearray(open(path, "rb").read())
        data[pos] ^= 0xFF
        open(path, "wb").write(bytes(data))
        assert cli.main(["fsck", "--wal", wal, "--backend", "cpu",
                         "--expect-clean"]) == 2


class TestFusedObservability:
    def test_stats_queries_and_check_cover_fused(self, tmp_path,
                                                 capsys):
        """/stats + /metrics export compress.fused.coverage and the
        devcache counters, /api/queries carries the fused-coverage
        block, and `tsdb check --stats-metric` thresholds it."""
        import asyncio
        import json as _json

        from tests.test_admission import (http_get, make_server,
                                          run_with_server)

        from opentsdb_tpu.tools.cli import main as cli_main
        server, tsdb = make_server(tmp_path, backend="tpu",
                                   sstable_codec="tsst4")
        rng = np.random.default_rng(3)
        for si in range(4):
            ts = BASE + np.arange(0, 12 * 3600, 300,
                                  dtype=np.int64) + si
            tsdb.add_batch("m.cpu", ts,
                           np.cumsum(rng.normal(0, 1, len(ts))),
                           {"host": f"h{si}"})
        tsdb.checkpoint()

        async def drive(port):
            sq, _, bq = await http_get(
                port, f"/q?start={BASE + 100}&end={BASE + 10 * 3600}"
                      "&m=sum:1h-avg:m.cpu&json&nocache")
            sa, _, ba = await http_get(port, "/stats?json")
            sp, _, bp = await http_get(port, "/metrics")
            sf, _, bf = await http_get(port, "/api/queries")
            loop = asyncio.get_running_loop()
            # Counters are process-global (other tests may have
            # recorded declines), so threshold at the extremes.
            rc_ok = await loop.run_in_executor(None, cli_main, [
                "check", "-H", "127.0.0.1", "-p", str(port),
                "--stats-metric", "tsd.compress.fused.coverage",
                "-x", "lt", "-c", "0.000001"])
            rc_bad = await loop.run_in_executor(None, cli_main, [
                "check", "-H", "127.0.0.1", "-p", str(port),
                "--stats-metric", "tsd.compress.fused.coverage",
                "-x", "ge", "-c", "0"])
            return (sq, bq), (sa, ba), (sp, bp), (sf, bf), \
                rc_ok, rc_bad

        (sq, bq), (sa, ba), (sp, bp), (sf, bf), rc_ok, rc_bad = \
            run_with_server(server, drive)
        tsdb.shutdown()
        assert sq == 200 and sa == 200 and sp == 200 and sf == 200
        lines = _json.loads(ba)
        cov = [ln for ln in lines
               if ln.startswith("tsd.compress.fused.coverage ")]
        assert cov and float(cov[0].split()[2]) > 0, cov
        assert any(ln.startswith("tsd.compress.devcache.hit ")
                   for ln in lines)
        assert any(ln.startswith("tsd.compress.devcache.miss ")
                   for ln in lines)
        assert b"compress_fused_coverage" in bp \
            or b"compress.fused.coverage" in bp
        feed = _json.loads(bf)
        assert feed["fused"]["attempt"] >= 1
        assert feed["fused"]["served"] >= 1
        assert 0 < feed["fused"]["coverage"] <= 1.0
        assert "devcache" in feed["fused"]
        assert rc_ok == 0 and rc_bad != 0
