"""Immutable sorted-table file: the spill tier under the memtable.

The reference delegates at-rest storage to HBase HFiles; here a
checkpoint spills the memtable into immutable generation files, after
which the WAL is truncated — bounding both recovery time and memtable
RAM for long-running daemons (SURVEY §5.4, §7.2: "enough LSM to sustain
ingest while scans run, without rebuilding HBase").

File layout v3 (all integers big-endian):
    magic  b"TSST3"
    record*  :=  [u16 table_len][table][u16 key_len][key][u32 ncells]
                 ([u16 fam_len][fam][u16 q_len][q][u32 v_len][v])*
    records sorted by (table, key); one record per row.
    footer   :=  per table:
                   [u16 table_len][table][u32 nkeys]
                   [key_lens: nkeys x u32][offsets: nkeys x u64]
                   [keys blob]
    bloom    :=  per table (same order as footer):
                   [u16 table_len][table][u8 k][u64 nbits][bits]
                   (k == 0, nbits == 0 => table has no bloom)
    trailer  :=  [u32 ntables][u64 footer_start][u64 bloom_start]

Format v4 (magic TSST4, Config.sstable_codec="tsst4") compresses the
record section as columnar BLOCKS (opentsdb_tpu/compress/codecs.py:
delta-of-delta timestamps + XOR floats / zigzag int deltas, zlib and
verbatim fallbacks — each block self-describing):
    magic  b"TSST4"
    block*   :=  [u8 codec_tag][u32 raw_len][u32 enc_len][enc bytes]
                 where the raw bytes are a run of same-table v3-framed
                 records
    footer   :=  [u32 raw_len][u32 enc_len][zlib of the v3 footer]
    blocks   :=  [u32 nblocks][raw_starts: u64 x n][file_starts: u64 x n]
    bloom    :=  identical to v3
    trailer  :=  [u32 ntables][u64 footer_start][u64 bloom_start]
                 [u64 blocks_start][u64 raw_end]
Footer offsets are RAW-space offsets — the offset each record would
have in the equivalent v3 file — so the index, ``record_extents`` and
the copy-merge all keep working in one coordinate system; the blocks
index maps raw offsets to file offsets, and readers decode whole
blocks lazily behind a small per-file cache. Mixed-format stores are
first-class: compaction re-encodes into whatever codec the writer is
configured for, and v1-v3 generations keep opening, serving and
merging forever.

The footer exists because opening a file by scanning every row record
cost ~3 us/row in Python — 10+ s per 4.4M-row generation, paid on every
checkpoint swap-in AND at every daemon start. It opens with two numpy
frombuffer calls and one C pass over the key blob. v2 files (magic
TSST2, no bloom section, 12-byte trailer) and v1 files (magic TSST1,
no footer, full-scan index) are still read; they simply never prune.

The bloom section holds one FIXED-SIZE (BLOOM_BITS) bloom filter per
table over the SERIES IDENTITIES of its row keys — metric UID + tag
UID pairs with the base-time bytes excluded, hashed with the same
crc32 chain the series sharder routes by — so shard fan-out readers
can skip whole generations that cannot contain any requested series
(query/executor._series_hint). Fixed-size on purpose: compaction
merges blooms by OR-ing the source generations' bit arrays instead of
re-hashing millions of relocated keys (only the frozen memtable's keys
— bounded per checkpoint — are ever hashed at write time). A table
whose source blooms are missing (v1/v2 input) or whose keys are too
short to carry a series identity gets k == 0: readers treat that as
"may contain anything".

The reader mmaps the file and keeps only (key -> offset) indexes in
RAM; cell payloads are decoded lazily per row, so a spilled store
serves gets and scans without rehydrating the dataset.
"""

from __future__ import annotations

import io
import mmap
import os
import struct
import threading
import zlib
from bisect import bisect_left, bisect_right
from typing import Iterable, Iterator

import numpy as np

from opentsdb_tpu.compress import codecs as _codecs
from opentsdb_tpu.core.const import TIMESTAMP_BYTES, UID_WIDTH
from opentsdb_tpu.fault import faultpoints as _fp
from opentsdb_tpu.fault.faultpoints import fire as _fault
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.utils.nativeext import ext as _EXT

_MAGIC_V1 = b"TSST1"
_MAGIC_V2 = b"TSST2"
_MAGIC = b"TSST3"
_MAGIC_V4 = b"TSST4"
_U16 = struct.Struct(">H")
_U32 = struct.Struct(">I")
_TRAILER = struct.Struct(">IQ")     # v2: ntables, footer_start
_TRAILER_V3 = struct.Struct(">IQQ")  # ntables, footer_start, bloom_start
# v4: ntables, footer_start, bloom_start, blocks_start, raw_end
_TRAILER_V4 = struct.Struct(">IQQQQ")
_BLOOM_HDR = struct.Struct(">BQ")   # k, nbits
_BLOCK_HDR = struct.Struct(">BII")  # codec tag, raw_len, enc_len

# Target UNCOMPRESSED bytes per v4 block: big enough that the columnar
# codecs amortize their per-block headers and numpy passes, small
# enough that a point-get decodes a bounded unit. Runs longer than
# this split at record boundaries.
BLOCK_RAW_TARGET = 1 << 18

# Pipelined spill encode (Config.spill_encode_workers): per-block
# TSST4 encoding — including the codec's self-check round-trip — runs
# on a small shared thread pool while the spill keeps framing the next
# run, so compression stops serializing behind the memtable freeze.
# Completed blocks drain strictly in submission order, so the file
# bytes (and the sst.write.block fault/flush cadence) are identical to
# the serial encode; the pool is simply bypassed while faultpoints are
# armed so crash schedules stay deterministic. 0 workers = serial.
_ENC_LOCK = threading.Lock()
_ENC_WORKERS = 0
_ENC_POOL = None
# Encoded-but-unwritten blocks allowed in flight per writer before the
# producer blocks on the oldest (bounds memory at a few raw blocks).
_ENC_MAX_PENDING = 4


def set_encode_workers(n: int) -> None:
    """Configure the shared encode pool (make_tsdb plumbs
    Config.spill_encode_workers here). Shrinking/zeroing takes effect
    for FUTURE _BodyWriters; an existing pool is retired lazily."""
    global _ENC_WORKERS, _ENC_POOL
    n = max(int(n), 0)
    with _ENC_LOCK:
        if n == _ENC_WORKERS:
            return
        old = _ENC_POOL
        _ENC_WORKERS = n
        _ENC_POOL = None
    if old is not None:
        old.shutdown(wait=False)


def _encode_pool():
    """The lazily created shared pool, or None when disabled."""
    global _ENC_POOL
    with _ENC_LOCK:
        if _ENC_WORKERS <= 0:
            return None
        if _ENC_POOL is None:
            from concurrent.futures import ThreadPoolExecutor
            _ENC_POOL = ThreadPoolExecutor(
                max_workers=min(_ENC_WORKERS, 4),
                thread_name_prefix="sst-encode")
        return _ENC_POOL

# Whole-block decode on the read path (scan, point get, copy-merge,
# fsck round-trip audits) — p50/p95/p99 + count via /stats + /metrics.
_M_DECODE = _metrics.timer("compress.decode")
# Blocks decoded by the STREAMING range sweep (iter_rows_range):
# decoded once into a local buffer and dropped as the sweep advances,
# never inserted into the per-file point-get cache.
_M_STREAM = _metrics.counter("compress.stream_blocks")

# Series-identity byte ranges of a data row key (the base-time bytes
# between them are excluded — the sharder's routing identity,
# storage/sharded.py _route). Keys shorter than _IDENT_HI carry no
# identity and make their table bloomless.
_IDENT_LO = UID_WIDTH
_IDENT_HI = UID_WIDTH + TIMESTAMP_BYTES

# Fixed per-table bloom geometry (see module docstring: fixed so
# compaction can OR source blooms). 2^20 bits = 128 KiB per table per
# generation; at 2k series and k=3 the false-positive rate is ~2e-7,
# and a false positive only costs one needless generation scan.
# K doubles as the bloom FORMAT discriminator: the reader ignores a
# stored bloom whose (k, nbits) mismatch the current geometry, so
# files written before the k=2->3 probe fix degrade to bloomless
# (never a false negative) and age out through compaction.
BLOOM_BITS = 1 << 20
BLOOM_K = 3

# Tests set this to 2 to produce bloomless legacy-format files; the
# reader handles both forever (mixed-format stores are first-class:
# old generations age out through compaction).
WRITE_FORMAT = 3

# row := (table, key, [(family, qualifier, value), ...])
Row = tuple[str, bytes, list[tuple[bytes, bytes, bytes]]]


def series_hash(series_key: bytes) -> int:
    """The 32-bit series-identity hash shared by the shard router, the
    sstable blooms, and the executor's candidate-series hint: crc32 of
    (metric UID + tag UID pairs). For a full ROW key, hash
    key[:_IDENT_LO] and key[_IDENT_HI:] chained — crc32 chaining equals
    crc32 of the concatenation, so both spellings agree."""
    return zlib.crc32(series_key)


def _bloom_positions(h1: "np.ndarray") -> "np.ndarray":
    """[n, BLOOM_K] bit positions from 32-bit identity hashes
    (Kirsch-Mitzenmacher). h2 MUST mix h1's HIGH bits: positions are
    taken mod the power-of-two BLOOM_BITS, so an h2 derived from h1
    by multiply-add alone is a pure function of h1 mod BLOOM_BITS and
    the extra probes add no independence (the original k=2 derivation
    behaved as k=1 — ~10x the theoretical false-positive rate under
    the hostile-cardinality regime). Deriving from h1 >> 16 (odd-
    forced so the k*h2 strides cycle the whole table) restores the
    (1 - e^{-kn/m})^k envelope; 32-bit identity collisions still
    collapse pairs — a handful of false positives at million-series
    scale, never a false negative."""
    h1 = h1.astype(np.uint64)
    h2 = ((h1 >> np.uint64(16)) * np.uint64(0x9E3779B1)
          + np.uint64(0x7FEB352D)) & np.uint64(0xFFFFFFFF)
    h2 = h2 | np.uint64(1)
    ks = np.arange(BLOOM_K, dtype=np.uint64)
    return (h1[:, None] + ks * h2[:, None]) % np.uint64(BLOOM_BITS)


def _bloom_bits_from_hashes(h1s: "list[int] | np.ndarray",
                            ) -> "np.ndarray":
    """BLOOM_BITS-bit array (packed uint8, little bit order) with the
    hashes' positions set."""
    bits = np.zeros(BLOOM_BITS, bool)
    if len(h1s):
        pos = _bloom_positions(np.asarray(h1s, np.uint64))
        bits[pos.ravel().astype(np.int64)] = True
    return np.packbits(bits, bitorder="little")


def _bloom_hashes_for_keys(keys: "Iterable[bytes]") -> "list[int] | None":
    """Identity hashes for a table's row keys; None when any key is too
    short to carry a series identity (that table gets no bloom — a
    filter that cannot cover every key would hide rows)."""
    crc = zlib.crc32
    out: set[int] = set()
    for k in keys:
        if len(k) < _IDENT_HI:
            return None
        out.add(crc(k[_IDENT_HI:], crc(k[:_IDENT_LO])))
    return list(out)


def _slice_varlen(blob: bytes, lens_be: bytes) -> list[bytes]:
    if _EXT is not None:
        return _EXT.slice_varlen(blob, lens_be)
    lens = np.frombuffer(lens_be, ">u4")
    ends = np.cumsum(lens)
    starts = ends - lens
    return [blob[a:b] for a, b in zip(starts.tolist(), ends.tolist())]


class _BodyWriter:
    """The record section of a new sstable, in either format: v2/v3
    writes records straight through (byte-identical to the historical
    layout), v4 ("tsst4" codec) accumulates same-table record runs and
    flushes them as self-describing compressed blocks.

    ``write_record``/``write_run`` return the RAW-space offset of the
    written bytes — the file offset in v2/v3, the virtual uncompressed
    offset in v4 — which is what the footer indexes and
    ``record_extents`` reports, so every consumer stays in one
    coordinate system regardless of format."""

    def __init__(self, f, codec: str | None) -> None:
        self.f = f
        self.v4 = codec == "tsst4"
        magic = _MAGIC_V4 if self.v4 \
            else (_MAGIC if WRITE_FORMAT >= 3 else _MAGIC_V2)
        f.write(magic)
        self.raw_off = len(magic)
        self._chunks: list[bytes] = []
        self._offs: list[int] = []
        self._pend = 0
        self._table: str | None = None
        self.blocks: list[tuple[int, int]] = []  # (raw_start, file_start)
        # Pipelined encode (set_encode_workers): in-flight
        # (raw_start, future) pairs, drained FIFO so file bytes match
        # the serial encode exactly. None = serial (v2/v3 format, pool
        # disabled, or faultpoints armed — the crash schedules count
        # fault firings, which must happen on the spilling thread in
        # deterministic order).
        self._futs = None
        if self.v4 and not _fp.active():
            pool = _encode_pool()
            if pool is not None:
                self._pool = pool
                from collections import deque
                self._futs = deque()

    def _append(self, table: str, buf: bytes, starts) -> int:
        """Queue record bytes for the current block; returns the raw
        offset of ``buf``'s first byte. A table switch flushes BEFORE
        queueing (one table per block) and raw_off only advances here,
        so a flush's raw_start accounting is exact either way."""
        if self._table is not None and self._table != table:
            self._flush_block()
        self._table = table
        base = self._pend
        self._offs.extend(int(s) + base for s in starts)
        self._chunks.append(buf)
        self._pend += len(buf)
        off = self.raw_off
        self.raw_off += len(buf)
        if self._pend >= BLOCK_RAW_TARGET:
            self._flush_block()
        return off

    def write_record(self, table: str, rec: bytes) -> int:
        if not self.v4:
            off = self.raw_off
            self.raw_off += len(rec)
            self.f.write(rec)
            return off
        return self._append(table, rec, (0,))

    def write_run(self, table: str, buf: bytes, starts) -> int:
        """A run of verbatim record bytes with known record ``starts``
        (relative to ``buf``, first at 0) — the copy-merge's unit. v4
        splits long runs at record boundaries near BLOCK_RAW_TARGET."""
        if not self.v4:
            off = self.raw_off
            self.raw_off += len(buf)
            self.f.write(buf)
            return off
        s = np.asarray(starts, np.int64)
        off0 = None
        i = 0
        while i < len(s):
            j = int(np.searchsorted(s, s[i] + BLOCK_RAW_TARGET, "left"))
            j = max(j, i + 1)
            end = int(s[j]) if j < len(s) else len(buf)
            lo = int(s[i])
            o = self._append(table, bytes(buf[lo:end]),
                             (s[i:j] - lo).tolist())
            if off0 is None:
                off0 = o - lo
            i = j
        return off0 if off0 is not None else self.raw_off

    def _flush_block(self) -> None:
        if not self._pend:
            return
        raw = self._chunks[0] if len(self._chunks) == 1 \
            else b"".join(self._chunks)
        raw_start = self.raw_off - self._pend
        self._chunks.clear()
        self._offs, offs = [], self._offs
        self._pend = 0
        self._table = None
        if self._futs is not None:
            self._futs.append((raw_start, self._pool.submit(
                _codecs.encode_block_split, raw, offs)))
            while len(self._futs) > _ENC_MAX_PENDING:
                self._write_parts(*self._futs.popleft(), blocking=True)
            return
        self._write_parts(raw_start,
                          _codecs.encode_block_split(raw, offs))

    def _write_parts(self, raw_start: int, parts,
                     blocking: bool = False) -> None:
        """Write one flushed run's encoded blocks (``parts`` is the
        encode_block_split result, or its future when pipelined)."""
        if blocking:
            parts = parts.result()
        # One flush may emit several physical blocks: a run mixing
        # value kinds at a metric boundary splits so each side keeps a
        # structured (fused-servable) codec instead of whole-run zlib.
        for rel, sub, tag, enc in parts:
            self.blocks.append((raw_start + rel, self.f.tell()))
            self.f.write(_BLOCK_HDR.pack(tag, len(sub), len(enc)))
            self.f.write(enc)
        # Compressed block body written, not yet durable: torn mode
        # cuts INSIDE this block specifically (header + payload), the
        # state a mid-spill power cut leaves — recovery must treat the
        # whole .tmp as a stray, never parse a half block. Flushed
        # first so the cut has on-disk bytes to land in (a block spans
        # many buffered-writer pages anyway).
        self.f.flush()
        _fault("sst.write.block", getattr(self.f, "name", None),
               _BLOCK_HDR.size + len(enc))

    def finish(self) -> int:
        """Flush pending blocks; returns the footer's file offset."""
        if self.v4:
            self._flush_block()
            while self._futs:
                self._write_parts(*self._futs.popleft(), blocking=True)
        return self.f.tell()


def _write_bloom_and_trailer(
        f, ntables: int, footer_start: int,
        blooms: "dict[str, np.ndarray | None]",
        bw: "_BodyWriter | None" = None) -> None:
    """Write the bloom section (format 3+) and the trailer, then make
    the file durable. ``blooms`` maps table -> packed bit array or
    None (no bloom); at WRITE_FORMAT 2 the section and the extended
    trailer fields are omitted entirely (legacy layout). ``bw`` (a v4
    body writer) adds the blocks index + the extended v4 trailer."""
    if bw is not None and bw.v4:
        blocks_start = f.tell()
        f.write(_U32.pack(len(bw.blocks)))
        f.write(np.asarray([b[0] for b in bw.blocks], ">u8").tobytes())
        f.write(np.asarray([b[1] for b in bw.blocks], ">u8").tobytes())
        bloom_start = f.tell()
        for table in sorted(blooms):
            tb = table.encode()
            bits = blooms[table]
            f.write(_U16.pack(len(tb)) + tb)
            if bits is None:
                f.write(_BLOOM_HDR.pack(0, 0))
            else:
                f.write(_BLOOM_HDR.pack(BLOOM_K, BLOOM_BITS))
                f.write(bits.tobytes())
        f.write(_TRAILER_V4.pack(ntables, footer_start, bloom_start,
                                 blocks_start, bw.raw_off))
    elif WRITE_FORMAT < 3:
        f.write(_TRAILER.pack(ntables, footer_start))
    else:
        bloom_start = f.tell()
        for table in sorted(blooms):
            tb = table.encode()
            bits = blooms[table]
            f.write(_U16.pack(len(tb)) + tb)
            if bits is None:
                f.write(_BLOOM_HDR.pack(0, 0))
            else:
                f.write(_BLOOM_HDR.pack(BLOOM_K, BLOOM_BITS))
                f.write(bits.tobytes())
        f.write(_TRAILER_V3.pack(ntables, footer_start, bloom_start))
    f.flush()
    # Footer + bloom + trailer written, not yet durable: torn mode
    # cuts INSIDE this section specifically (rec_bytes spans exactly
    # the bytes since footer_start), leaving a body-complete file
    # whose index is garbage — the reader/recovery must treat it as a
    # stray .tmp, never parse a half footer.
    _fault("sst.write.footer", getattr(f, "name", None),
           max(f.tell() - footer_start, 1))
    os.fsync(f.fileno())


def _footer_bytes(index: dict[str, tuple[list[bytes], list[int]]],
                  ) -> bytes:
    out = io.BytesIO()
    for table in sorted(index):
        keys, offs = index[table]
        tb = table.encode()
        out.write(_U16.pack(len(tb)) + tb + _U32.pack(len(keys)))
        out.write(np.fromiter(map(len, keys), ">u4",
                              len(keys)).tobytes())
        out.write(np.asarray(offs, ">u8").tobytes())
        out.write(b"".join(keys))
    return out.getvalue()


def _finish_file(f, index: dict[str, tuple[list[bytes], list[int]]],
                 footer_start: int,
                 blooms: "dict[str, np.ndarray | None] | None" = None,
                 bw: "_BodyWriter | None" = None,
                 ) -> None:
    """Write the footer (+ blocks index + bloom section + trailer) and
    make the file durable. ``blooms`` overrides the per-table bloom
    bits (the copy-merge passes OR-ed source blooms); by default each
    table's bloom is built from its index keys. A v4 ``bw`` stores the
    footer zlib-compressed (the per-key index is ~25 B/row of highly
    redundant keys/offsets — left raw it would cap the whole file's
    compression ratio)."""
    if bw is not None and bw.v4:
        fb = _footer_bytes(index)
        z = zlib.compress(fb, 1)
        f.write(_U32.pack(len(fb)) + _U32.pack(len(z)) + z)
    else:
        # Streamed (not buffered): a 4M-row generation's footer is
        # ~100 MB and the v3 path must not grow a peak-RSS bump.
        for table in sorted(index):
            keys, offs = index[table]
            tb = table.encode()
            f.write(_U16.pack(len(tb)) + tb + _U32.pack(len(keys)))
            f.write(np.fromiter(map(len, keys), ">u4",
                                len(keys)).tobytes())
            f.write(np.asarray(offs, ">u8").tobytes())
            f.write(b"".join(keys))
    if blooms is None:
        blooms = {}
        for table, (keys, _) in index.items():
            hs = _bloom_hashes_for_keys(keys)
            blooms[table] = (None if hs is None
                             else _bloom_bits_from_hashes(hs))
    else:
        # One bloom entry per indexed table, always (the reader parses
        # the section by the trailer's table count).
        blooms = {t: blooms.get(t) for t in index}
    _write_bloom_and_trailer(f, len(index), footer_start, blooms, bw)


def _durable_rename(tmp: str, path: str) -> None:
    # Body complete in the page cache, not yet renamed: crash leaves a
    # .tmp recovery ignores; torn cuts into the record/footer section
    # (same outcome — the cut file never gets renamed).
    _fault("sst.write.body", tmp, 1 << 12)
    os.replace(tmp, path)
    # Rename visible, directory entry not yet fsynced: on process
    # death (os._exit) the rename IS visible — the interesting state
    # for crash recovery, which must treat the new file as a stray
    # until a manifest names it.
    _fault("sst.rename", path)
    # Make the rename itself durable before the caller truncates its
    # WAL: without the directory fsync a power loss could surface the
    # OLD generation alongside an already-truncated WAL.
    dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def write_sstable_bulk(path: str,
                       tables: dict[str, tuple[list[bytes], object]],
                       codec: str | None = None) -> int:
    """write_sstable for pre-materialized data: per table, a SORTED key
    list and either a parallel list of cell lists OR the memtable row
    dict itself (key -> {(fam, qual): value}, no tombstones). With the
    native extension the whole record section frames in one C pass per
    table (the per-row Python framing was ~5 us/row — the dominant cost
    of checkpoint spills at scale); without it, falls back to the
    streaming writer. A compressed ``codec`` always streams: blocks
    need per-record boundaries the C framer doesn't report."""
    if _EXT is None or codec == "tsst4":
        def rows():
            for table in sorted(tables):
                keys, data = tables[table]
                if isinstance(data, dict):
                    for k in keys:
                        yield table, k, sorted(
                            (f, q, v)
                            for (f, q), v in data[k].items())
                else:
                    for k, c in zip(keys, data):
                        yield table, k, c
        return write_sstable(path, rows(), codec=codec)
    tmp = path + ".tmp"
    n = 0
    with open(tmp, "wb") as f:
        f.write(_MAGIC if WRITE_FORMAT >= 3 else _MAGIC_V2)
        off = len(_MAGIC)
        footer: dict[str, tuple[bytes, bytes, list[bytes]]] = {}
        for table in sorted(tables):
            keys, data = tables[table]
            if isinstance(data, dict):
                recs, offs_be, klens_be = _EXT.frame_rows_dict(
                    table.encode(), keys, data, off)
            else:
                recs, offs_be, klens_be = _EXT.frame_rows(
                    table.encode(), keys, data, off)
            f.write(recs)
            off += len(recs)
            n += len(keys)
            footer[table] = (offs_be, klens_be, keys)
        footer_start = off
        blooms: dict[str, "np.ndarray | None"] = {}
        for table in sorted(footer):
            offs_be, klens_be, keys = footer[table]
            tb = table.encode()
            f.write(_U16.pack(len(tb)) + tb + _U32.pack(len(keys)))
            f.write(klens_be)
            f.write(offs_be)
            f.write(b"".join(keys))
            hs = _bloom_hashes_for_keys(keys)
            blooms[table] = (None if hs is None
                             else _bloom_bits_from_hashes(hs))
        _write_bloom_and_trailer(f, len(footer), footer_start, blooms)
    _durable_rename(tmp, path)
    return n


def write_sstable(path: str, rows: Iterable[Row],
                  codec: str | None = None) -> int:
    """Write rows (pre-sorted by (table, key)) to a new sstable at `path`.

    Returns the number of rows written. Writes via a temp file + atomic
    rename so a crash mid-write never corrupts the previous generation.
    ``codec`` "tsst4" writes format v4 (compressed blocks); None/"none"
    writes the WRITE_FORMAT legacy layout byte-identically.
    """
    tmp = path + ".tmp"
    n = 0
    index: dict[str, tuple[list[bytes], list[int]]] = {}
    with open(tmp, "wb") as f:
        bw = _BodyWriter(f, codec)
        for table, key, cells in rows:
            tb = table.encode()
            parts = [_U16.pack(len(tb)), tb, _U16.pack(len(key)), key,
                     _U32.pack(len(cells))]
            for fam, qual, value in cells:
                parts += [_U16.pack(len(fam)), fam, _U16.pack(len(qual)),
                          qual, _U32.pack(len(value)), value]
            off = bw.write_record(table, b"".join(parts))
            keys, offs = index.setdefault(table, ([], []))
            keys.append(key)
            offs.append(off)
            n += 1
        _finish_file(f, index, bw.finish(), bw=bw)
    _durable_rename(tmp, path)
    return n


def _frame_record(table_b: bytes, key: bytes,
                  cells: dict) -> bytes:
    """One record from a cell dict ({(fam, qual): value}, no Nones),
    cells sorted — same wire layout as write_sstable's loop."""
    triples = sorted((f, q, v) for (f, q), v in cells.items())
    parts = [_U16.pack(len(table_b)), table_b, _U16.pack(len(key)), key,
             _U32.pack(len(triples))]
    for fam, qual, value in triples:
        parts += [_U16.pack(len(fam)), fam, _U16.pack(len(qual)), qual,
                  _U32.pack(len(value)), value]
    return b"".join(parts)


def merge_sstables(path: str, gens: "list[SSTable]",
                   frozen: dict, codec: str | None = None) -> int:
    """Collapse sstable generations (OLDEST FIRST) + a frozen memtable
    tier into one new sstable at ``path`` — the full-merge leg of
    checkpoint (storage/kv.py), rebuilt as a COPY-MERGE.

    ``frozen``: {table: (rows, row_tombs, has_cell_tombs)} with rows =
    {key: {(fam, qual): value-or-None}} (None = tombstone masking a
    lower generation) and row_tombs masking whole lower-tier rows.

    Keys present in exactly one generation and untouched by the frozen
    tier — at scale, nearly all of them (time-major ingest puts each
    row-hour in one spill) — have their record bytes copied VERBATIM,
    contiguous runs as single slices, so the merge runs at IO speed.
    Only multi-source keys and frozen rows are decoded and re-framed
    (tombstones applied). The previous streamed per-row merge paid a
    per-key binary search per generation plus Python framing for every
    row: 20.7 us/row, 145 s for a 7M-row merge measured at the 1B
    400M-point mark; the copy path is two orders cheaper.
    Returns rows written. Same tmp + fsync + atomic-rename durability
    contract as write_sstable. ``codec`` selects the OUTPUT format;
    compaction re-encodes as it merges, so mixed-format generation
    sets converge on the writer's configured codec (v4 sources feeding
    a v4 output decode + re-compress block-wise; the unique-key record
    bytes themselves still relocate verbatim, never re-frame).
    """
    names = set(frozen)
    for g in gens:
        names.update(g.tables())
    tmp = path + ".tmp"
    n = 0
    index: dict[str, tuple[list[bytes], list[int]]] = {}
    blooms: dict[str, "np.ndarray | None"] = {}
    with open(tmp, "wb") as f:
        bw = _BodyWriter(f, codec)
        for name in sorted(names):
            rows_f, row_tombs, has_tombs = frozen.get(
                name, ({}, set(), False))
            tb = name.encode()
            extents = [g.record_extents(name) for g in gens]
            # Multi-source keys: seen in >1 generation, or overlaid by
            # a frozen row. (Running set-union dup detection; the
            # per-table transient is ~O(total keys).)
            seen: set[bytes] = set()
            dup: set[bytes] = set()
            for keys, _, _ in extents:
                ks = set(keys)
                dup |= seen & ks
                seen |= ks
            dup.update(k for k in rows_f if k in seen)
            pairs: list[tuple[bytes, int]] = []
            # 1) Verbatim copy of single-source, frozen-untouched runs.
            # Vectorized segmentation: a per-key Python loop (set
            # probes + numpy scalar int conversions + a tuple genexpr)
            # cost ~2.2 us/key — 39 s of a 127 s profile at 17.5M rows.
            # Here the skipped keys (dup/row-tomb, both small sets) are
            # located by bisect, file-contiguity breaks (key order !=
            # file order in a previously-merged generation) come from
            # one numpy compare, and each surviving segment costs one
            # slice write + one vector add, with C-speed zip for the
            # footer pairs.
            skip = dup | row_tombs
            for (keys, starts, ends), g in zip(extents, gens):
                m = len(keys)
                if m == 0:
                    continue
                excl = set()
                if skip:
                    for k in skip:
                        p = bisect_left(keys, k)
                        if p < m and keys[p] == k:
                            excl.add(p)
                breaks = np.nonzero(starts[1:] != ends[:-1])[0] + 1
                cuts = np.unique(np.concatenate([
                    np.array([0, m], np.int64), breaks,
                    np.fromiter(excl, np.int64, len(excl)),
                    np.fromiter((p + 1 for p in excl), np.int64,
                                len(excl))]))
                for a, b in zip(cuts[:-1].tolist(), cuts[1:].tolist()):
                    if a in excl:
                        continue
                    lo, hi = int(starts[a]), int(ends[b - 1])
                    run_off = bw.write_run(name, g.raw_bytes(lo, hi),
                                           starts[a:b] - lo)
                    pairs.extend(zip(
                        keys[a:b],
                        (starts[a:b] + (run_off - lo)).tolist()))
            # 2) Multi-source keys: overlay oldest -> newest -> frozen.
            for k in dup:
                merged: dict = {}
                if k not in row_tombs:
                    for g in gens:
                        cells = g.get(name, k)
                        if cells:
                            for fam, q, v in cells:
                                merged[(fam, q)] = v
                row = rows_f.get(k)
                if row:
                    for ck, v in row.items():
                        if v is None:
                            merged.pop(ck, None)
                        else:
                            merged[ck] = v
                if not merged:
                    continue
                rec = _frame_record(tb, k, merged)
                pairs.append((k, bw.write_record(name, rec)))
            # 3) Frozen-only rows (C-framed when tombstone-free).
            fr_only = sorted(k for k in rows_f
                             if k not in dup and rows_f[k])
            if fr_only and _EXT is not None and not has_tombs:
                base = bw.raw_off
                recs, offs_be, _ = _EXT.frame_rows_dict(
                    tb, fr_only, rows_f, base)
                abs_offs = np.frombuffer(offs_be, ">u8").astype(
                    np.int64)
                bw.write_run(name, recs, abs_offs - base)
                pairs.extend(zip(fr_only, abs_offs.tolist()))
            else:
                for k in fr_only:
                    cells = {ck: v for ck, v in rows_f[k].items()
                             if v is not None}
                    if not cells:
                        continue
                    rec = _frame_record(tb, k, cells)
                    pairs.append((k, bw.write_record(name, rec)))
            if not pairs:
                continue
            # Timsort exploits the concatenated sorted runs.
            pairs.sort()
            index[name] = ([p[0] for p in pairs], [p[1] for p in pairs])
            n += len(pairs)
            # Bloom for the merged table: OR the source generations'
            # fixed-size blooms (records relocate verbatim, so their
            # identities carry over; keys a tombstone just dropped
            # leave stale bits — false positives only) and hash in the
            # frozen tier's keys. Any bloomless source (v1/v2 file,
            # short keys) makes the output bloomless: a bloom that
            # does not cover every key would hide rows from pruned
            # scans.
            bloom: "np.ndarray | None" = np.zeros(BLOOM_BITS // 8,
                                                 np.uint8)
            for g in gens:
                if g.key_count(name) == 0:
                    continue
                gb = g.bloom_bits(name)
                if gb is None:
                    bloom = None
                    break
                np.bitwise_or(bloom, gb, out=bloom)
            if bloom is not None and rows_f:
                hs = _bloom_hashes_for_keys(rows_f)
                if hs is None:
                    bloom = None
                else:
                    np.bitwise_or(bloom, _bloom_bits_from_hashes(hs),
                                  out=bloom)
            blooms[name] = bloom
        _finish_file(f, index, bw.finish(), blooms, bw=bw)
    _durable_rename(tmp, path)
    return n


class SSTable:
    """mmap-backed reader over one sstable generation."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._f = open(path, "rb")
        size = os.fstat(self._f.fileno()).st_size
        self._mm = mmap.mmap(self._f.fileno(), size, access=mmap.ACCESS_READ)
        # table -> (sorted keys, parallel row offsets)
        self._index: dict[str, tuple[list[bytes], list[int]]] = {}
        # table -> packed BLOOM_BITS bit array (absent = no pruning)
        self._blooms: dict[str, np.ndarray] = {}
        self._all_starts = None  # record_extents' sorted-start cache
        # v4 state: raw-space block starts (python list for bisect),
        # parallel file offsets, and a tiny decoded-block FIFO (scans
        # walk blocks sequentially, so a handful of slots turns the
        # per-row decode into one vectorized pass per block).
        self._blk_raw: list[int] | None = None
        self._blk_file: list[int] | None = None
        self._blk_cache: dict[int, bytes] = {}
        self.format = 3
        head = self._mm[:len(_MAGIC)]
        if head == _MAGIC_V4:
            self.format = 4
            self._load_footer(v3=True, v4=True)
        elif head == _MAGIC:
            self._load_footer(v3=True)
        elif head == _MAGIC_V2:
            self.format = 2
            self._load_footer(v3=False)
        elif head == _MAGIC_V1:
            self.format = 1
            self._build_index_v1()
        else:
            raise IOError(f"{path}: bad sstable magic")

    def _load_footer(self, v3: bool, v4: bool = False) -> None:
        mm = self._mm
        if v4:
            (ntables, footer_start, bloom_start, blocks_start,
             raw_end) = _TRAILER_V4.unpack_from(
                mm, len(mm) - _TRAILER_V4.size)
            self._data_end = raw_end
            self._footer_file_start = footer_start
            # Blocks index: raw-space starts + file offsets.
            (nblocks,) = _U32.unpack_from(mm, blocks_start)
            off = blocks_start + 4
            self._blk_raw = np.frombuffer(
                mm, ">u8", nblocks, off).astype(np.int64).tolist()
            off += 8 * nblocks
            self._blk_file = np.frombuffer(
                mm, ">u8", nblocks, off).astype(np.int64).tolist()
            # Footer: one zlib unit of the v3 footer bytes.
            fb_raw, fb_enc = _U32.unpack_from(mm, footer_start)[0], \
                _U32.unpack_from(mm, footer_start + 4)[0]
            fbuf = zlib.decompress(
                mm[footer_start + 8:footer_start + 8 + fb_enc])
            if len(fbuf) != fb_raw:
                raise IOError(f"{self.path}: footer decompressed to "
                              f"{len(fbuf)} bytes, expected {fb_raw}")
            src, off = fbuf, 0
        elif v3:
            ntables, footer_start, bloom_start = _TRAILER_V3.unpack_from(
                mm, len(mm) - _TRAILER_V3.size)
            self._data_end = footer_start
            src, off = mm, footer_start
        else:
            ntables, footer_start = _TRAILER.unpack_from(
                mm, len(mm) - _TRAILER.size)
            bloom_start = None
            self._data_end = footer_start
            src, off = mm, footer_start
        for _ in range(ntables):
            (tlen,) = _U16.unpack_from(src, off)
            off += 2
            table = src[off:off + tlen].decode()
            off += tlen
            (nkeys,) = _U32.unpack_from(src, off)
            off += 4
            lens_be = src[off:off + 4 * nkeys]
            off += 4 * nkeys
            offs = np.frombuffer(src, ">u8", nkeys, off).tolist()
            off += 8 * nkeys
            blob_len = int(np.frombuffer(lens_be, ">u4").sum())
            keys = _slice_varlen(src[off:off + blob_len], lens_be)
            off += blob_len
            self._index[table] = (keys, offs)
        if bloom_start is not None:
            off = bloom_start
            for _ in range(ntables):
                (tlen,) = _U16.unpack_from(mm, off)
                off += 2
                table = mm[off:off + tlen].decode()
                off += tlen
                k, nbits = _BLOOM_HDR.unpack_from(mm, off)
                off += _BLOOM_HDR.size
                nbytes = nbits >> 3
                if k:
                    # Copied out of the mmap (a frombuffer VIEW would
                    # pin the map open past close()); 128 KiB per
                    # table.
                    bits = np.frombuffer(mm, np.uint8, nbytes,
                                         off).copy()
                    off += nbytes
                    # Foreign geometry (a build with different BLOOM
                    # consts) reads fine but cannot be probed or
                    # OR-merged — treat as bloomless.
                    if k == BLOOM_K and nbits == BLOOM_BITS:
                        self._blooms[table] = bits

    def _build_index_v1(self) -> None:
        self._data_end = len(self._mm)
        mm, off, end = self._mm, len(_MAGIC_V1), len(self._mm)
        while off < end:
            start = off
            (tlen,) = _U16.unpack_from(mm, off)
            off += 2
            table = mm[off:off + tlen].decode()
            off += tlen
            (klen,) = _U16.unpack_from(mm, off)
            off += 2
            key = bytes(mm[off:off + klen])
            off += klen
            (ncells,) = _U32.unpack_from(mm, off)
            off += 4
            for _ in range(ncells):
                (flen,) = _U16.unpack_from(mm, off)
                off += 2 + flen
                (qlen,) = _U16.unpack_from(mm, off)
                off += 2 + qlen
                (vlen,) = _U32.unpack_from(mm, off)
                off += 4 + vlen
            keys, offs = self._index.setdefault(table, ([], []))
            keys.append(key)
            offs.append(start)

    def close(self) -> None:
        self._mm.close()
        self._f.close()

    def tables(self) -> list[str]:
        return list(self._index)

    def key_count(self, table: str) -> int:
        idx = self._index.get(table)
        return len(idx[0]) if idx else 0

    def key_bounds(self, table: str) -> tuple[bytes, bytes] | None:
        """(smallest, largest) row key stored for ``table``, or None
        when the table is absent — a batch existence prefilter: keys
        outside this range cannot be in the sstable, which lets
        time-ordered ingest (new base-times sort after every spilled
        key) skip the per-key bisect entirely."""
        idx = self._index.get(table)
        if not idx or not idx[0]:
            return None
        keys = idx[0]
        return keys[0], keys[-1]

    def bloom_bits(self, table: str) -> "np.ndarray | None":
        """Packed bloom bit array for ``table`` (the copy-merge ORs
        these), or None when the table has no usable bloom."""
        return self._blooms.get(table)

    def bloom_may_contain(self, table: str,
                          h1s: "np.ndarray") -> bool:
        """Can this generation hold ANY series whose identity hash is
        in ``h1s`` (uint64 array of series_hash values)? True when the
        table has no bloom (v1/v2 file, short keys, foreign geometry)
        — absence of evidence never prunes."""
        bits = self._blooms.get(table)
        if bits is None or len(h1s) == 0:
            return True
        pos = _bloom_positions(h1s)
        got = (bits[(pos >> np.uint64(3)).astype(np.int64)]
               >> (pos & np.uint64(7)).astype(np.uint8)) & 1
        return bool(got.all(axis=1).any())

    def bloom_may_contain_hash(self, table: str, h1: int) -> bool:
        """Scalar bloom probe for ONE series-identity hash — the
        point-get prefilter (_lower_tier_has skips this generation's
        key bisect on False). Pure-int arithmetic, exactly
        _bloom_positions' Kirsch-Mitzenmacher derivation, so it can
        never disagree with the vectorized scan-path probe. True when
        the table has no bloom."""
        bits = self._blooms.get(table)
        if bits is None:
            return True
        h2 = (((h1 >> 16) * 0x9E3779B1 + 0x7FEB352D)
              & 0xFFFFFFFF) | 1
        for k in range(BLOOM_K):
            pos = (h1 + k * h2) % BLOOM_BITS
            if not (bits[pos >> 3] >> (pos & 7)) & 1:
                return False
        return True

    def bloom_check(self, table: str) -> "int | None":
        """fsck probe: how many of the table's indexed keys are NOT
        covered by its bloom (must be 0 — a false negative silently
        hides rows from pruned scans). None when the table has no
        bloom."""
        bits = self._blooms.get(table)
        if bits is None:
            return None
        idx = self._index.get(table)
        if not idx or not idx[0]:
            return 0
        hs = _bloom_hashes_for_keys(idx[0])
        if hs is None:
            # Short keys under a bloom: every such key is invisible to
            # bloom-pruned scans — count them all as misses.
            return sum(1 for k in idx[0] if len(k) < _IDENT_HI)
        pos = _bloom_positions(np.asarray(hs, np.uint64))
        got = (bits[(pos >> np.uint64(3)).astype(np.int64)]
               >> (pos & np.uint64(7)).astype(np.uint8)) & 1
        return int((~got.all(axis=1)).sum())

    def has_key(self, table: str, key: bytes) -> bool:
        idx = self._index.get(table)
        if not idx:
            return False
        keys, _ = idx
        i = bisect_left(keys, key)
        return i < len(keys) and keys[i] == key

    # -- v4 block access ------------------------------------------------

    @property
    def block_count(self) -> int:
        return len(self._blk_raw) if self._blk_raw is not None else 0

    def block_header(self, j: int) -> tuple[int, int, int]:
        """(codec tag, raw_len, enc_len) of block ``j``."""
        return _BLOCK_HDR.unpack_from(self._mm, self._blk_file[j])

    def block_raw_span(self, j: int) -> tuple[int, int]:
        """[raw_start, raw_end) of block ``j`` in raw space."""
        lo = self._blk_raw[j]
        hi = self._blk_raw[j + 1] if j + 1 < len(self._blk_raw) \
            else self._data_end
        return lo, hi

    def block_enc(self, j: int) -> memoryview:
        """The encoded payload bytes of block ``j`` (no copy)."""
        tag, raw_len, enc_len = self.block_header(j)
        start = self._blk_file[j] + _BLOCK_HDR.size
        return memoryview(self._mm)[start:start + enc_len]

    def _block_raw(self, j: int) -> bytes:
        """Decoded raw record bytes of block ``j``, behind a small
        FIFO cache (scans touch blocks in order; dict ops are
        GIL-atomic, so concurrent scans at worst decode twice)."""
        got = self._blk_cache.get(j)
        if got is not None:
            return got
        tag, raw_len, enc_len = self.block_header(j)
        with _M_DECODE.time():
            raw = _codecs.decode_block(tag, self.block_enc(j), raw_len)
        if len(self._blk_cache) >= 8:
            try:
                self._blk_cache.pop(next(iter(self._blk_cache)))
            except (StopIteration, KeyError):
                pass
        self._blk_cache[j] = raw
        return raw

    def _record_buf(self, off: int):
        """(buffer, position) holding the record at raw offset ``off``
        — the mmap itself on raw formats, the decoded enclosing block
        on v4."""
        if self._blk_raw is None:
            return self._mm, off
        j = bisect_right(self._blk_raw, off) - 1
        return self._block_raw(j), off - self._blk_raw[j]

    def raw_bytes(self, lo: int, hi: int) -> bytes:
        """Raw record bytes [lo, hi) in raw space — what the copy-merge
        relocates. v4 concatenates decoded block slices."""
        if self._blk_raw is None:
            return self._mm[lo:hi]
        if hi <= lo:
            return b""
        j = bisect_right(self._blk_raw, lo) - 1
        parts = []
        while lo < hi:
            blo, bhi = self.block_raw_span(j)
            raw = self._block_raw(j)
            parts.append(raw[lo - blo:min(hi, bhi) - blo])
            lo = bhi
            j += 1
        return parts[0] if len(parts) == 1 else b"".join(parts)

    def codec_stats(self) -> "tuple[int, int] | None":
        """(raw_bytes, stored_bytes) of the record section — the
        compression ratio source. None on non-v4 files."""
        if self._blk_raw is None:
            return None
        return (self._data_end - len(_MAGIC_V4),
                self._footer_file_start - len(_MAGIC_V4))

    def block_audit(self, log=None) -> int:
        """fsck's block check: every block's codec tag must be known,
        its payload must decode, and the decoded size must match the
        header's uncompressed size. Returns the error count."""
        errors = 0
        say = log if log is not None else (lambda *_: None)
        if self._blk_raw is None:
            return 0
        for j in range(self.block_count):
            lo, hi = self.block_raw_span(j)
            try:
                tag, raw_len, enc_len = self.block_header(j)
            except struct.error:
                errors += 1
                say(f"ERROR: {self.path}: block {j}: truncated header")
                continue
            if raw_len != hi - lo:
                errors += 1
                say(f"ERROR: {self.path}: block {j}: header raw_len "
                    f"{raw_len} != index span {hi - lo}")
                continue
            try:
                raw = _codecs.decode_block(tag, self.block_enc(j),
                                           raw_len)
            except _codecs.BlockCodecError as e:
                errors += 1
                say(f"ERROR: {self.path}: block {j} "
                    f"(tag={tag}): {e}")
                continue
            del raw
        return errors

    def _read_row(self, off: int) -> list[tuple[bytes, bytes, bytes]]:
        mm, off = self._record_buf(off)
        return self._parse_row(mm, off)

    @staticmethod
    def _parse_row(mm, off: int) -> list[tuple[bytes, bytes, bytes]]:
        (tlen,) = _U16.unpack_from(mm, off)
        off += 2 + tlen
        (klen,) = _U16.unpack_from(mm, off)
        off += 2 + klen
        (ncells,) = _U32.unpack_from(mm, off)
        off += 4
        cells = []
        for _ in range(ncells):
            (flen,) = _U16.unpack_from(mm, off)
            off += 2
            fam = bytes(mm[off:off + flen])
            off += flen
            (qlen,) = _U16.unpack_from(mm, off)
            off += 2
            qual = bytes(mm[off:off + qlen])
            off += qlen
            (vlen,) = _U32.unpack_from(mm, off)
            off += 4
            value = bytes(mm[off:off + vlen])
            off += vlen
            cells.append((fam, qual, value))
        return cells

    def get(self, table: str,
            key: bytes) -> list[tuple[bytes, bytes, bytes]] | None:
        """Cells of one row, or None when the key is absent."""
        idx = self._index.get(table)
        if not idx:
            return None
        keys, offs = idx
        i = bisect_left(keys, key)
        if i >= len(keys) or keys[i] != key:
            return None
        return self._read_row(offs[i])

    def scan_keys(self, table: str, start: bytes,
                  stop: bytes | None) -> list[bytes]:
        idx = self._index.get(table)
        if not idx:
            return []
        keys, _ = idx
        lo = bisect_left(keys, start)
        hi = bisect_left(keys, stop) if stop else len(keys)
        return keys[lo:hi]

    def range_count(self, table: str, start: bytes,
                    stop: bytes | None) -> int:
        """len(scan_keys(table, start, stop)) without the slice."""
        idx = self._index.get(table)
        if not idx:
            return 0
        keys, _ = idx
        hi = bisect_left(keys, stop) if stop else len(keys)
        return hi - bisect_left(keys, start)

    def record_extents(self, table: str) -> tuple[
            "list[bytes]", "np.ndarray", "np.ndarray"]:
        """(sorted keys, record starts, record ends) for one table.

        Records carry no embedded offsets, so a [start, end) byte
        slice relocates verbatim into another file — the basis of the
        copy-merge compaction (merge_sstables), which moves unique-key
        records at IO speed instead of decode/re-frame speed. Every
        writer appends records back-to-back, but NOT necessarily in
        key order (merge_sstables scatters re-framed rows after the
        copy runs), so each record's end is the smallest record start
        greater than its own — computed against the file's full start
        set, with the record section's end as the sentinel.
        """
        idx = self._index.get(table)
        if not idx or not idx[0]:
            e = np.empty(0, np.int64)
            return [], e, e
        keys, offs = idx
        starts = np.asarray(offs, dtype=np.int64)
        all_starts = self._all_starts
        if all_starts is None:
            all_starts = np.sort(np.concatenate(
                [np.asarray(o, dtype=np.int64)
                 for _, o in self._index.values()]
                + [np.asarray([self._data_end], dtype=np.int64)]))
            self._all_starts = all_starts
        ends = all_starts[np.searchsorted(all_starts, starts, "right")]
        return keys, starts, ends

    def iter_rows_range(self, table: str, start: bytes,
                        stop: bytes | None,
                        skip: "set[bytes] | None" = None) -> Iterator[
            tuple[bytes, list[tuple[bytes, bytes, bytes]]]]:
        """Rows with start <= key < stop (stop None = to the end), in
        key order — the range form of the read path. One bisect pair
        per CALL instead of one per key: the cold scan used to probe
        every generation per row-hour (2.35M get() calls over a 1-week
        scan of the 1B store, ~5 s of the 17 s wall). ``skip`` (e.g.
        the caller's row-tombstone set) suppresses rows BEFORE the
        record decode — masked rows cost a set probe, not a full
        _read_row."""
        idx = self._index.get(table)
        if not idx:
            return
        keys, offs = idx
        lo = bisect_left(keys, start)
        hi = bisect_left(keys, stop) if stop else len(keys)
        if self._blk_raw is not None and hi - lo > 1:
            yield from self._stream_rows(keys, offs, lo, hi, skip)
            return
        if skip:
            for i in range(lo, hi):
                if keys[i] not in skip:
                    yield keys[i], self._read_row(offs[i])
        else:
            for i in range(lo, hi):
                yield keys[i], self._read_row(offs[i])

    def _stream_rows(self, keys, offs, lo: int, hi: int, skip):
        """Chunked/streamed decode for v4 range sweeps (replica
        refresh refolds, rollup catch-up scans, full-store sketch
        rebuilds): rows are grouped by their enclosing block and each
        block decodes ONCE into a LOCAL buffer, dropped as the sweep
        advances — peak decode memory is one block (vs filling and
        churning the 8-slot cache), the per-row block bisect
        disappears, and the point-get cache keeps its query working
        set (a whole-generation sweep never evicts it). A cached
        block is reused but a streamed decode is never inserted."""
        j = -1
        braw: bytes | None = None
        blo = bhi = 0
        for i in range(lo, hi):
            if skip and keys[i] in skip:
                continue
            off = offs[i]
            if not blo <= off < bhi or braw is None:
                j = bisect_right(self._blk_raw, off) - 1
                blo, bhi = self.block_raw_span(j)
                braw = self._blk_cache.get(j)
                if braw is None:
                    tag, raw_len, _enc = self.block_header(j)
                    with _M_DECODE.time():
                        braw = _codecs.decode_block(
                            tag, self.block_enc(j), raw_len)
                    _M_STREAM.inc()
            yield keys[i], self._parse_row(braw, off - blo)

    def iter_rows(self, table: str) -> Iterator[
            tuple[bytes, list[tuple[bytes, bytes, bytes]]]]:
        idx = self._index.get(table)
        if not idx:
            return
        keys, offs = idx
        if self._blk_raw is not None and len(keys) > 1:
            yield from self._stream_rows(keys, offs, 0, len(keys),
                                         None)
            return
        for key, off in zip(keys, offs):
            yield key, self._read_row(off)
