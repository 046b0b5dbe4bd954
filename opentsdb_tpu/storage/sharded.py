"""Series-sharded multi-writer storage: N independent KVStore shards.

The reference gets horizontal write scaling for free from HBase region
partitioning on the metric-first row key (reference
src/core/IncomingDataPoints.java); this engine funneled every write
through one ``MemKVStore`` — one memtable lock, one WAL, one sstable
generation tier — so at the 1B+ scale the checkpoint spill/merge of the
WHOLE history is one stall that every writer waits behind
(checkpoint.spill + checkpoint.wait + kv.put_batch, and one long pause
whenever a tiered collapse lands).

``ShardedKVStore`` partitions rows by a stable hash of the row key's
SERIES identity (metric UID + tag UID pairs — the base-time bytes are
excluded, so every row-hour of one series lands in the same shard, the
moral analog of the reference's salt+metric region prefix) into N
independent ``MemKVStore`` shards, each with its own memtable, WAL, and
sstable generation tier under ``<dir>/shard-<i>/``:

- **Ingest** routes columnar batches to shards WITHOUT re-encoding:
  ``add_batch`` sends one series per ``put_many_columnar`` call, so the
  whole key blob flows to a single shard (and into its columnar WAL
  record) untouched; mixed batches split into per-shard sub-blobs by
  numpy row indexing, still columnar.
- **Checkpoint** runs every shard's 3-phase spill in a bounded worker
  pool: each freeze is its own brief per-shard lock, the phase-2
  sstable writes overlap, and — because each shard holds ~1/N of the
  history and the generation caps are STAGGERED across shards (shard i
  caps at base+i, so size-tiered collapses fire on different
  checkpoints) — the worst-case mid-ingest pause becomes the largest
  single *shard's* merge instead of the whole history's.
- **Reads** fan a scan out across shards and merge the ordered
  per-shard iterators (keys are disjoint across shards by routing
  determinism, so the merge is a pure interleave); gets/atomics route
  point-wise.

Durability/consistency model: each shard is exactly a ``MemKVStore``
(crash-replay per shard WAL, per-shard manifest, per-shard flock); the
shard count and routing parameters are pinned by an atomically-written
``SHARDS.json`` at the store root, and reopening with a different
count is a hard error (rows would silently route to the wrong shard).
There is no cross-shard atomic cut: a checkpoint freezes shards a few
microseconds apart and a crash recovers each shard to its own last
durable record — the same weak cross-row guarantees one HBase region
server gives relative to another.
"""

from __future__ import annotations

import heapq
import json
import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import numpy as np

from opentsdb_tpu.core.const import TIMESTAMP_BYTES, UID_WIDTH
from opentsdb_tpu.core.errors import PleaseThrottleError
from opentsdb_tpu.fault import faultpoints as _fp
from opentsdb_tpu.obs import trace as _trace
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.storage.kv import Cell, KVStore, MemKVStore
from opentsdb_tpu.storage.sstable import series_hash

MANIFEST_NAME = "SHARDS.json"

# Byte range of the row key holding the base time (excluded from the
# routing hash so a series never straddles shards).
_TS_LO = UID_WIDTH
_TS_HI = UID_WIDTH + TIMESTAMP_BYTES


def manifest_path(dir_path: str) -> str:
    return os.path.join(dir_path, MANIFEST_NAME)


class ShardedKVStore(KVStore):
    """N series-hash-partitioned MemKVStore shards behind one KVStore.

    ``dir_path=None`` builds an in-memory (non-persistent) sharded
    store — no WALs, no manifest — for benchmarks and tests.

    ``partial_existed`` semantics differ from MemKVStore on a mid-batch
    ``PleaseThrottleError``: cells route to shards out of input order,
    so the attached list is FULL-LENGTH (one flag per input cell) with
    ``False`` for cells that did not apply, rather than an
    applied-prefix. Callers that use the flags to queue compactions
    (the only current consumer) stay exact: every ``True`` cell
    applied onto an existing row.
    """

    def __init__(self, dir_path: str | None, shards: int | None = None,
                 data_table: str = "tsdb",
                 throttle_rows: int | None = None, fsync: bool = False,
                 read_only: bool = False,
                 spill_workers: int | None = None,
                 writer_epoch: int | None = None,
                 epoch_guard=None) -> None:
        self._dir = dir_path
        self.read_only = read_only
        self.data_table = data_table
        # Cluster write tier: ONE epoch (EPOCH.json at the store root,
        # next to SHARDS.json) covers all shards — they live and die
        # with the writer process as a unit — and one guard is shared
        # across every shard's mutation path.
        self.writer_epoch = writer_epoch
        self.epoch_guard = epoch_guard
        # Whole shards dropped from a fan-out by the series-hint
        # routing prefilter (scan_raw).
        self.bloom_shards_skipped = 0
        created_manifest = False
        if dir_path is not None:
            man = manifest_path(dir_path)
            if os.path.exists(man):
                with open(man) as f:
                    rec = json.load(f)
                n_disk = int(rec["shards"])
                if shards is not None and shards != n_disk:
                    raise ValueError(
                        f"shard-count mismatch: store at {dir_path!r} "
                        f"was created with {n_disk} shards, reopen "
                        f"requested {shards} (rows would route to the "
                        f"wrong shard; re-shard via export/import)")
                if rec.get("data_table", data_table) != data_table:
                    raise ValueError(
                        f"data-table mismatch: store at {dir_path!r} "
                        f"routes table {rec['data_table']!r} by series, "
                        f"reopen requested {data_table!r}")
                # Routing parameters are load-bearing exactly like the
                # count: a build whose key layout hashes different
                # byte ranges would silently route point ops to the
                # wrong shard (reads come back empty, writes diverge).
                if rec.get("version", 1) != 1 or list(
                        rec.get("series_bytes_excluded",
                                [_TS_LO, _TS_HI])) != [_TS_LO, _TS_HI]:
                    raise ValueError(
                        f"routing mismatch: store at {dir_path!r} was "
                        f"created with manifest version "
                        f"{rec.get('version')} / series bytes "
                        f"{rec.get('series_bytes_excluded')}, this "
                        f"build routes with v1 / {[_TS_LO, _TS_HI]}")
                n = n_disk
            else:
                if read_only:
                    raise FileNotFoundError(
                        f"no {MANIFEST_NAME} at {dir_path!r}: a replica "
                        f"cannot create a sharded store")
                if shards is None:
                    raise ValueError(
                        f"no {MANIFEST_NAME} at {dir_path!r} and no "
                        f"shard count given")
                n = shards
                self._write_manifest(dir_path, n, data_table)
                created_manifest = True
        else:
            if shards is None:
                raise ValueError("in-memory sharded store needs an "
                                 "explicit shard count")
            n = shards
        if n < 1:
            raise ValueError(f"shard count must be >= 1, got {n}")
        self.shard_count = n
        self._spill_workers = (spill_workers if spill_workers
                               else min(n, max(os.cpu_count() or 2, 2)))
        # Sketch-snapshot naming root (TSDB._sketch_path): the snapshot
        # is store-global (folded above the shard layer), so it lives
        # beside the manifest, not inside any shard.
        self._wal_path = (os.path.join(dir_path, "store")
                         if dir_path else None)
        per_throttle = (None if throttle_rows is None
                        else max((throttle_rows + n - 1) // n, 1))
        self.shards: list[MemKVStore] = []
        try:
            for i in range(n):
                wal = (os.path.join(dir_path, f"shard-{i}", "wal")
                       if dir_path else None)
                # Staggered generation caps (base + i, bounded): every
                # shard receives ~1/N of each spill, so with EQUAL caps
                # all shards would hit the size-tiered collapse on the
                # SAME checkpoint and the pauses would re-align into
                # one full-history-sized stall. Distinct caps offset
                # each shard's collapse schedule by whole checkpoints.
                self.shards.append(MemKVStore(
                    wal_path=wal, throttle_rows=per_throttle,
                    fsync=fsync, read_only=read_only,
                    max_generations=(MemKVStore._MAX_GENERATIONS
                                     + i % min(n, 8)),
                    writer_epoch=writer_epoch,
                    epoch_guard=epoch_guard))
        except BaseException:
            for s in self.shards:
                try:
                    s.close()
                except Exception:
                    pass
            if created_manifest:
                # First-time creation failed (stale shard lock, ENOSPC
                # mid-open): remove the manifest we just wrote, or it
                # would permanently pin a shard count for a store that
                # holds no data and hard-error every retry with a
                # different N.
                try:
                    os.unlink(manifest_path(dir_path))
                except OSError:
                    pass
            raise

    @staticmethod
    def _write_manifest(dir_path: str, n: int, data_table: str) -> None:
        """Atomically pin the shard layout (tmp + rename + dir fsync,
        the same durability contract as the per-shard manifests)."""
        os.makedirs(dir_path, exist_ok=True)
        man = manifest_path(dir_path)
        tmp = man + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "shards": n,
                       "data_table": data_table,
                       "series_bytes_excluded": [_TS_LO, _TS_HI]}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, man)
        dfd = os.open(dir_path, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    # -- routing ----------------------------------------------------------

    def _route(self, table: str, key: bytes) -> int:
        """Stable shard index for a key. Data-table keys hash their
        series bytes (metric UID + tag pairs, base time excluded) so
        all hours of a series co-locate; everything else (UID table,
        short keys) hashes the whole key. crc32, not hash(): routing
        must be identical across processes and restarts."""
        if self.shard_count == 1:
            return 0
        if table == self.data_table and len(key) >= _TS_HI:
            h = zlib.crc32(key[_TS_HI:], zlib.crc32(key[:_TS_LO]))
        else:
            h = zlib.crc32(key)
        return h % self.shard_count

    # -- point ops (route + delegate) -------------------------------------

    def get(self, table: str, key: bytes,
            family: bytes | None = None) -> list[Cell]:
        return self.shards[self._route(table, key)].get(table, key, family)

    def has_row(self, table: str, key: bytes) -> bool:
        return self.shards[self._route(table, key)].has_row(table, key)

    def cell_count(self, table: str, key: bytes) -> int:
        return self.shards[self._route(table, key)].cell_count(table, key)

    def row_count(self, table: str) -> int:
        return sum(s.row_count(table) for s in self.shards)

    def put(self, table: str, key: bytes, family: bytes, qualifier: bytes,
            value: bytes, durable: bool = True) -> None:
        self.shards[self._route(table, key)].put(
            table, key, family, qualifier, value, durable=durable)

    def delete(self, table: str, key: bytes, family: bytes,
               qualifiers: list[bytes]) -> None:
        self.shards[self._route(table, key)].delete(
            table, key, family, qualifiers)

    def delete_row(self, table: str, key: bytes) -> None:
        self.shards[self._route(table, key)].delete_row(table, key)

    def atomic_increment(self, table: str, key: bytes, family: bytes,
                         qualifier: bytes, amount: int = 1) -> int:
        return self.shards[self._route(table, key)].atomic_increment(
            table, key, family, qualifier, amount)

    def compare_and_set(self, table: str, key: bytes, family: bytes,
                        qualifier: bytes, expected: bytes | None,
                        value: bytes) -> bool:
        return self.shards[self._route(table, key)].compare_and_set(
            table, key, family, qualifier, expected, value)

    # -- batched writes ----------------------------------------------------

    def put_many(self, table: str, family: bytes,
                 cells: list[tuple[bytes, bytes, bytes]],
                 durable: bool = True, sync: bool = True) -> list[bool]:
        if self.shard_count == 1:
            return self.shards[0].put_many(table, family, cells,
                                           durable=durable, sync=sync)
        by_shard: dict[int, list[int]] = {}
        for i, (key, _, _) in enumerate(cells):
            by_shard.setdefault(self._route(table, key), []).append(i)
        existed = [False] * len(cells)
        for si in sorted(by_shard):
            idx = by_shard[si]
            sub = [cells[i] for i in idx]
            try:
                flags = self.shards[si].put_many(table, family, sub,
                                                 durable=durable,
                                                 sync=sync)
            except PleaseThrottleError as e:
                part = getattr(e, "partial_existed", [])
                for i, f in zip(idx, part):
                    existed[i] = f
                e.partial_existed = existed  # full-length (see class doc)
                raise
            for i, f in zip(idx, flags):
                existed[i] = f
        return existed

    def put_many_columnar(self, table: str, family: bytes,
                          key_blob: bytes, key_len: int,
                          quals: list[bytes], vals: list[bytes],
                          durable: bool = True,
                          sync: bool = True) -> list[bool]:
        n = len(quals)
        if len(vals) != n or len(key_blob) != n * key_len:
            raise ValueError(
                f"columnar batch mismatch: {len(key_blob)} key bytes, "
                f"key_len {key_len}, {n} quals, {len(vals)} vals")
        if n == 0:
            return []
        if self.shard_count == 1:
            return self.shards[0].put_many_columnar(
                table, family, key_blob, key_len, quals, vals,
                durable=durable, sync=sync)
        L = key_len
        # Same-series fast path — the add_batch hot shape: one series
        # per batch, keys differing only in their base-time bytes. One
        # vectorized equality check, one route, and the key blob flows
        # through to the shard's columnar WAL record UNCHANGED.
        if table == self.data_table and L >= _TS_HI:
            mat = np.frombuffer(key_blob, np.uint8).reshape(n, L)
            same = bool(
                (mat[:, :_TS_LO] == mat[0, :_TS_LO]).all()
                and (mat[:, _TS_HI:] == mat[0, _TS_HI:]).all())
        else:
            mat = np.frombuffer(key_blob, np.uint8).reshape(n, L)
            first = key_blob[:L]
            same = n == 1 or key_blob == first * n
        if same:
            return self.shards[self._route(table, key_blob[:L])] \
                .put_many_columnar(table, family, key_blob, L, quals,
                                   vals, durable=durable, sync=sync)
        # Mixed batch: route per key, regroup into per-shard sub-blobs
        # (numpy row gather keeps them columnar — no per-cell tuples).
        routes = np.fromiter(
            (self._route(table, key_blob[i * L:(i + 1) * L])
             for i in range(n)), np.int64, n)
        existed = [False] * n
        for si in np.unique(routes):
            idx = np.flatnonzero(routes == si)
            sub_blob = mat[idx].tobytes()
            sub_q = [quals[i] for i in idx]
            sub_v = [vals[i] for i in idx]
            try:
                flags = self.shards[int(si)].put_many_columnar(
                    table, family, sub_blob, L, sub_q, sub_v,
                    durable=durable, sync=sync)
            except PleaseThrottleError as e:
                part = getattr(e, "partial_existed", [])
                for i, f in zip(idx.tolist(), part):
                    existed[i] = f
                e.partial_existed = existed
                raise
            for i, f in zip(idx.tolist(), flags):
                existed[i] = f
        return existed

    # -- scans (cross-shard fan-in) ----------------------------------------

    def scan(self, table: str, start: bytes, stop: bytes,
             family: bytes | None = None,
             key_regexp: bytes | None = None) -> Iterator[list[Cell]]:
        """Ordered fan-in: merge every shard's already-sorted scan.
        Routing determinism makes shard key sets disjoint, so the merge
        is a pure interleave (no cross-shard row merging). Snapshot
        semantics are per shard — exactly the weak cross-region
        guarantees an HBase multi-region scan gives."""
        its = [s.scan(table, start, stop, family=family,
                      key_regexp=key_regexp) for s in self.shards]
        return heapq.merge(*its, key=lambda cells: cells[0].key)

    def scan_raw(self, table: str, start: bytes, stop: bytes,
                 family: bytes | None = None,
                 key_regexp: bytes | None = None,
                 series_hint=None, series_keys=None,
                 ) -> Iterator[tuple[bytes, list[tuple[bytes, bytes]]]]:
        """Fan-in scan; with a ``series_hint`` (uint64 series-identity
        hashes, a superset of the series the caller keeps) the fan-out
        first drops shards no candidate routes to — the routing hash
        IS the identity hash (sstable.series_hash), so ``h % N`` is
        exact, not probabilistic — then each shard's own series blooms
        prune generations. ``series_keys`` go to the shards by the
        same routing: each seeks, or walks, its own rows."""
        n = self.shard_count
        live = list(range(n))
        keys_of = [series_keys] * n
        if table == self.data_table and n > 1:
            if series_hint is not None and len(series_hint):
                live = np.unique(series_hint % np.uint64(n)).tolist()
                self.bloom_shards_skipped += n - len(live)
            if series_keys is not None:
                keys_of = [[] for _ in range(n)]
                for k in series_keys:
                    keys_of[series_hash(k) % n].append(k)
        its = [self.shards[i].scan_raw(
            table, start, stop, family=family, key_regexp=key_regexp,
            series_hint=series_hint, series_keys=keys_of[i])
            for i in live]
        parent = _trace.current_span()
        if parent is not None:
            # Per-shard fan-out spans: each shard's span accumulates
            # only the time spent pulling from THAT shard's iterator
            # (the heap merge interleaves them), attached to the span
            # current at fan-out time when its iterator is exhausted.
            its = [_trace.timed_iter(it, parent, "shard.scan",
                                     {"shard": i})
                   for it, i in zip(its, live)]
            if len(live) < n:
                parent.tags["shards_skipped"] = n - len(live)
        return heapq.merge(*its, key=lambda row: row[0])

    # -- memtable introspection (sketch recovery re-fold) ------------------

    def memtable_keys(self, table: str) -> list[bytes]:
        out: list[bytes] = []
        for s in self.shards:
            out.extend(s.memtable_keys(table))
        return out

    def memtable_row_counts(self, table: str) -> list[int]:
        """Live-memtable row count per shard (the /stats gauge)."""
        return [s.memtable_row_counts(table)[0] for s in self.shards]

    @property
    def sstable_codec(self) -> str:
        return self.shards[0].sstable_codec if self.shards else "none"

    @sstable_codec.setter
    def sstable_codec(self, codec: str) -> None:
        for s in self.shards:
            s.sstable_codec = codec

    @property
    def wal_group_ms(self) -> float:
        return self.shards[0].wal_group_ms if self.shards else 0.0

    @wal_group_ms.setter
    def wal_group_ms(self, ms: float) -> None:
        for s in self.shards:
            s.wal_group_ms = ms

    def wal_barrier(self, ticket: int | None = None) -> None:
        """Group-commit barrier across every shard (per-shard tickets
        are not comparable store-wide, so the fan-out always waits for
        each shard's own current watermark)."""
        for s in self.shards:
            s.wal_barrier()

    def sstable_format_bytes(self) -> dict[int, int]:
        out: dict[int, int] = {}
        for s in self.shards:
            for fmt, n in s.sstable_format_bytes().items():
                out[fmt] = out.get(fmt, 0) + n
        return out

    def compress_stats(self) -> tuple[int, int]:
        raw = enc = 0
        for s in self.shards:
            r, e = s.compress_stats()
            raw += r
            enc += e
        return raw, enc

    def encoded_range(self, table: str, start: bytes,
                      stop: bytes | None):
        """Per-shard encoded_range fan-in (see MemKVStore): shards are
        key-disjoint by the series routing, so the union of per-shard
        disjoint spans is disjoint. None if any shard declines."""
        out = []
        for s in self.shards:
            got = s.encoded_range(table, start, stop)
            if got is None:
                return None
            out.extend(got)
        return out

    def pending_keys(self, table: str) -> list[bytes]:
        out: list[bytes] = []
        for s in self.shards:
            out.extend(s.pending_keys(table))
        return out

    def peek_spill_keys(self) -> dict[str, list[bytes]]:
        out: dict[str, list[bytes]] = {}
        for s in self.shards:
            for name, ks in s.peek_spill_keys().items():
                out.setdefault(name, []).extend(ks)
        return out

    def take_spill_keys(self) -> dict[str, list[bytes]]:
        out: dict[str, list[bytes]] = {}
        for s in self.shards:
            for name, ks in s.take_spill_keys().items():
                out.setdefault(name, []).extend(ks)
        return out

    @property
    def mutation_seq(self) -> int:
        return sum(s.mutation_seq for s in self.shards)

    @property
    def mutation_seqs(self) -> tuple[int, ...]:
        """Per-shard mutation sequence vector: lets consumers
        revalidate per shard instead of treating one put anywhere as
        invalidating everything (the summed ``mutation_seq`` above)."""
        return tuple(s.mutation_seq for s in self.shards)

    def dirty_bases(self, table: str) -> np.ndarray:
        """Union of every shard's incrementally-maintained dirty-base
        set (see MemKVStore.dirty_bases), sorted unique."""
        arrs = [a for a in (s.dirty_bases(table) for s in self.shards)
                if len(a)]
        if not arrs:
            return np.empty(0, np.int64)
        if len(arrs) == 1:
            return arrs[0]
        return np.unique(np.concatenate(arrs))

    def chunk_state(self, table: str, lo: int, hi: int):
        """Per-shard fragment-cache validation vectors (see
        MemKVStore.chunk_state); ``dirty`` is the OR across shards —
        a fan-in fragment merges every shard's rows, so one dirty
        shard taints the chunk."""
        epochs: list[int] = []
        floors: list[int] = []
        marks: list[int] = []
        dirty = False
        for s in self.shards:
            e, f, m, d = s.chunk_state(table, lo, hi)
            epochs.extend(e)
            floors.extend(f)
            marks.extend(m)
            dirty = dirty or d
        return tuple(epochs), tuple(floors), tuple(marks), dirty

    @property
    def record_spill_keys(self) -> bool:
        return all(s.record_spill_keys for s in self.shards)

    @record_spill_keys.setter
    def record_spill_keys(self, value: bool) -> None:
        for s in self.shards:
            s.record_spill_keys = value

    @property
    def delete_hook(self):
        return self.shards[0].delete_hook if self.shards else None

    @delete_hook.setter
    def delete_hook(self, fn) -> None:
        for s in self.shards:
            s.delete_hook = fn

    @property
    def spilled(self) -> bool:
        return any(s.spilled for s in self.shards)

    def memtable_cells(self, table: str, key: bytes,
                       family: bytes | None = None) -> list[Cell]:
        return self.shards[self._route(table, key)].memtable_cells(
            table, key, family)

    # -- lifecycle ---------------------------------------------------------

    def ensure_table(self, table: str) -> None:
        for s in self.shards:
            s.ensure_table(table)

    def flush(self) -> None:
        for s in self.shards:
            s.flush()

    def checkpoint(self) -> int:
        """Checkpoint every shard, phase-2 spills overlapped in a
        bounded worker pool. Each shard's freeze/swap is its own brief
        lock (ingest to OTHER shards never waits even for that), and
        the expensive merges run concurrently — the worst-case pause a
        writer can observe is one shard's largest merge, ~1/N of the
        single-store history collapse. Returns total rows spilled."""
        if self.read_only:
            return 0
        if _fp.active():
            # Fault injection armed: spill serially so the failpoint
            # hit schedule (and therefore the crash state) is
            # deterministic — which shard a count=k crash lands after
            # must not depend on pool scheduling. The per-shard join
            # site fires AFTER each shard's spill completes, so a
            # count=k crash leaves exactly k shards spilled and N-k
            # still WAL-only (the no-cross-shard-atomic-cut contract
            # the crash matrix verifies).
            total = 0
            for i, s in enumerate(self.shards):
                total += self._timed_spill(i, s)
                _fp.fire("sharded.spill.shard", self._dir)
            return total
        if self.shard_count == 1 or self._spill_workers <= 1:
            return sum(self._timed_spill(i, s)
                       for i, s in enumerate(self.shards))
        with ThreadPoolExecutor(
                max_workers=self._spill_workers,
                thread_name_prefix="shard-spill") as pool:
            return sum(pool.map(self._timed_spill,
                                range(self.shard_count), self.shards))

    @staticmethod
    def _timed_spill(i: int, shard: MemKVStore) -> int:
        """One shard's checkpoint, timed per shard (the join a writer
        can block on is one shard's largest merge — the per-shard
        timer is what makes staggered-compaction skew visible)."""
        with _metrics.timer("checkpoint.shard_spill",
                            {"shard": str(i)}).time():
            return shard.checkpoint()

    def refresh(self) -> bool:
        """Replica catch-up across every shard (each shard's refresh is
        the plain MemKVStore suffix-replay-or-rebuild)."""
        changed = False
        for s in self.shards:
            changed |= s.refresh()
        return changed

    @property
    def rebuilds(self) -> int:
        return sum(s.rebuilds for s in self.shards)

    @property
    def bloom_files_skipped(self) -> int:
        return sum(s.bloom_files_skipped for s in self.shards)

    @property
    def bloom_point_skips(self) -> int:
        return sum(s.bloom_point_skips for s in self.shards)

    @property
    def wal_swallowed_flush_errors(self) -> int:
        return sum(s.wal_swallowed_flush_errors for s in self.shards)

    def close(self) -> None:
        first: BaseException | None = None
        for s in self.shards:
            try:
                s.close()
            except BaseException as e:
                # Close EVERY shard even when one fails (a shard left
                # open wedges later reopens on its flock); surface the
                # first failure after the sweep.
                if first is None:
                    first = e
        if first is not None:
            raise first

    def _simulate_crash(self) -> None:
        """TEST HOOK: process-death simulation across all shards (see
        MemKVStore._simulate_crash)."""
        for s in self.shards:
            s._simulate_crash()

    # -- cluster promotion / demotion (cluster/) --------------------------

    def promote_writable(self, writer_epoch: int,
                         epoch_guard=None) -> None:
        """Replica promotion across every shard (each shard runs the
        MemKVStore fresh-inode takeover). A shard that fails to
        promote demotes the already-promoted prefix back — the store
        comes out all-writer or all-replica, never mixed."""
        done: list[MemKVStore] = []
        try:
            for s in self.shards:
                s.promote_writable(writer_epoch,
                                   epoch_guard=epoch_guard)
                done.append(s)
        except BaseException:
            for s in done:
                try:
                    s.demote_readonly()
                except Exception:
                    pass
            raise
        self.read_only = False
        self.writer_epoch = int(writer_epoch)
        self.epoch_guard = epoch_guard

    def demote_readonly(self) -> None:
        for s in self.shards:
            s.demote_readonly()
        self.read_only = True
        self.writer_epoch = None
        self.epoch_guard = None

    @property
    def fenced_bytes_refused(self) -> int:
        return sum(s.fenced_bytes_refused for s in self.shards)
