"""Ordered key-value store: in-memory memtable + append-only WAL.

This engine stands in for the HBase cluster of the reference deployment. The
API surface is intentionally the exact set of primitives OpenTSDB uses via
asynchbase (reference src/core/TSDB.java:479-494 get/put/delete;
src/uid/UniqueId.java:243,297,326 atomicIncrement/compareAndSet;
src/core/TsdbQuery.java:368-492 ordered scan + key regexp), so the layers
above translate one-to-one while staying storage-agnostic behind ``KVStore``.

Design notes (TPU-first, not an HBase rebuild):
- Rows live in a dict keyed by row key; each row is a dict keyed by
  (family, qualifier). Scans sort lazily: the sorted key index is rebuilt
  only when a scan happens after inserts, keeping the hot ingest path O(1)
  per put — the analog of an LSM memtable without the merge machinery.
- Durability is an append-only WAL with length-prefixed records, replayed on
  open. ``durable=False`` puts skip the WAL (batch-import mode, parity with
  setDurable(false), reference IncomingDataPoints.java:253).
- Backpressure: once the row count crosses ``throttle_rows``, writes raise
  PleaseThrottleError until a flush/compaction shrinks it — the analog of
  HBase's PleaseThrottleException signal.
- Checkpoint/resume (SURVEY §5.4): ``checkpoint()`` merges the memtable
  (plus the previous spill generation) into one immutable sorted sstable
  (storage/sstable.py), then truncates the WAL — bounding recovery time
  and memtable RAM. On open: load sstable, then replay the WAL suffix.
  Reads merge the tiers, memtable winning; deletes over spilled rows
  leave tombstones (cell tombstone = None value; row tombstones in
  ``_Table.row_tombs``) so compaction's put-then-delete-originals cycle
  stays correct across the spill boundary.
- Checkpoint does NOT stall ingest: under the lock it only freezes the
  current memtable as an immutable middle tier and rotates the WAL
  (pre-checkpoint records move to ``<wal>.old``); the dataset merge and
  sstable write run outside the lock while writes land in a fresh
  memtable + fresh WAL; a second brief lock swaps generations and
  removes ``<wal>.old``. Crash at any point recovers by replaying
  ``<wal>.old`` then the WAL over whichever sstable generation survived
  — replay is idempotent (puts rewrite equal values, deletes re-create
  tombstones, counter increments are logged as absolute values).
"""

from __future__ import annotations

import fcntl
import io
import logging
import os
import re
import struct
import threading
import zlib
from time import perf_counter as _perf
from bisect import bisect_left
from typing import Iterator, NamedTuple

import numpy as np

from opentsdb_tpu.core.const import (MAX_TIMESPAN, TIMESTAMP_BYTES,
                                     UID_WIDTH)
from opentsdb_tpu.core.errors import (PleaseThrottleError,
                                       ReadOnlyStoreError)
from opentsdb_tpu.fault.faultpoints import fire as _fault
from opentsdb_tpu.obs import trace as _trace
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.storage.sstable import (SSTable, merge_sstables,
                                          write_sstable_bulk)
from opentsdb_tpu.utils.nativeext import ext as _EXT

_REC = struct.Struct(">BI")  # op, payload length

# Engine instruments (obs/registry.py): registered once at import, so
# the hot paths pay one attribute increment / one perf_counter pair
# per WAL *batch* or checkpoint phase — never per point.
_M_WAL_APPENDS = _metrics.counter("wal.appends")
_M_WAL_BYTES = _metrics.counter("wal.append_bytes")
_M_WAL_APPEND = _metrics.timer("wal.append")
_M_WAL_FSYNC = _metrics.timer("wal.fsync")
# Group commit (Config.wal_group_ms): batches = append calls whose
# flush was deferred to a group leader, points = WAL records inside
# them, fsyncs = covering group flushes, wait_ms = time ack paths
# spent parked in the barrier.
_M_GRP_BATCHES = _metrics.counter("wal.group.batches")
_M_GRP_POINTS = _metrics.counter("wal.group.points")
_M_GRP_FSYNCS = _metrics.counter("wal.group.fsyncs")
_M_GRP_WAIT = _metrics.timer("wal.group.wait_ms")
# Selective scans (scan_raw with a key regexp) by how they found their
# rows: point lookups of the candidate keys, or the walk over every key
# of the range. Their sum is the count of such scans.
_M_SCAN_SEEK = _metrics.counter("scan.seek")
_M_SCAN_WALK = _metrics.counter("scan.walk")
# Observed through _trace.timed (a timer and a profiler annotation of
# the same name); registered here so that /stats lists them from boot.
for _ph in ("freeze", "spill", "commit"):
    _metrics.timer("checkpoint.phase", {"phase": _ph})

# Row-key byte range holding the base time (data-table layout,
# core/codec.row_key). The incremental dirty-base index slices it per
# NEW ROW so consumers (the rollup planner's dirty-window set, the
# executor's fragment cache) never have to sweep the whole key list;
# keys too short to carry it (UID-table names, stray tool deletes) are
# simply not indexed — matching the sweep's own filter.
_BASE_LO = UID_WIDTH
_BASE_HI = UID_WIDTH + TIMESTAMP_BYTES
# A selective scan seeks where its probes (candidate keys x tiers to
# bisect) times this margin are no more than the keys the walk would
# list. A probe that finds nothing costs about what a listed key does (a
# bisect against a few membership tests and a regexp match), so with
# the margin a seek is the cheaper way even if every probe misses, and
# a selector that names most of the range (host=*) stays on the walk.
_SEEK_MARGIN = 4


class Cell(NamedTuple):
    key: bytes
    family: bytes
    qualifier: bytes
    value: bytes


class KVStore:
    """Abstract ordered-KV interface; see MemKVStore for the semantics."""

    def get(self, table: str, key: bytes,
            family: bytes | None = None) -> list[Cell]:
        raise NotImplementedError

    def has_row(self, table: str, key: bytes) -> bool:
        return bool(self.get(table, key))

    def put(self, table: str, key: bytes, family: bytes, qualifier: bytes,
            value: bytes, durable: bool = True) -> None:
        raise NotImplementedError

    def put_many(self, table: str, family: bytes,
                 cells: list[tuple[bytes, bytes, bytes]],
                 durable: bool = True, sync: bool = True) -> list[bool]:
        """Write (key, qualifier, value) cells; returns, per cell, True
        when the row holds other cells by the time this one lands —
        either it existed before the batch, or an earlier cell of the
        batch already hit it (both mean the caller must queue
        compaction). On PleaseThrottleError mid-batch the exception's
        ``partial_existed`` carries the flags for the cells that DID
        apply. Default loops over put(); MemKVStore overrides with a
        single-lock batch. ``sync=False`` defers the WAL group-commit
        wait (stores without group commit ignore it): the caller must
        issue ``wal_barrier()`` before acknowledging.
        """
        existed: list[bool] = []
        seen: set[bytes] = set()
        for key, qualifier, value in cells:
            try:
                prior = key in seen or self.has_row(table, key)
                self.put(table, key, family, qualifier, value, durable)
            except PleaseThrottleError as e:
                e.partial_existed = existed
                raise
            existed.append(prior)
            seen.add(key)
        return existed

    def put_many_columnar(self, table: str, family: bytes,
                          key_blob: bytes, key_len: int,
                          quals: list[bytes], vals: list[bytes],
                          durable: bool = True,
                          sync: bool = True) -> list[bool]:
        """put_many with columnar inputs: cell i's key is the i-th
        ``key_len``-byte slice of ``key_blob``. Semantics identical to
        ``put_many`` on the zipped triples; exists so the batch ingest
        hot path (core/tsdb.py add_batch) never materializes a
        per-cell tuple list. Default zips and delegates; MemKVStore
        overrides with bulk dict operations and a columnar WAL record."""
        keys = [key_blob[i:i + key_len]
                for i in range(0, key_len * len(quals), key_len)]
        return self.put_many(table, family, list(zip(keys, quals, vals)),
                             durable=durable, sync=sync)

    def delete(self, table: str, key: bytes, family: bytes,
               qualifiers: list[bytes]) -> None:
        raise NotImplementedError

    def delete_row(self, table: str, key: bytes) -> None:
        raise NotImplementedError

    def scan(self, table: str, start: bytes, stop: bytes,
             family: bytes | None = None,
             key_regexp: bytes | None = None) -> Iterator[list[Cell]]:
        raise NotImplementedError

    def scan_raw(self, table: str, start: bytes, stop: bytes,
                 family: bytes | None = None,
                 key_regexp: bytes | None = None,
                 series_hint: "np.ndarray | None" = None,
                 series_keys: "list[bytes] | None" = None,
                 ) -> Iterator[tuple[bytes, list[tuple[bytes, bytes]]]]:
        """Scan for bulk decode: (key, [(qualifier, value), ...]) rows,
        qualifiers sorted — no Cell objects. Default adapts scan();
        stores override with a batched implementation (the columnar
        read path calls this per row-HOUR, so per-row allocation and
        locking overhead multiplies by the whole scanned range).

        ``series_hint``: optional uint64 array of series-identity
        hashes (sstable.series_hash) that is a SUPERSET of the series
        the caller will keep — a pure pruning hint. Stores may use it
        to skip sstable generations (bloom prefilter) or whole shards
        (routing); ignoring it is always correct.

        ``series_keys``: optional, with a ``key_regexp`` over a
        metric's base hours: the series keys (core/codec.series_key)
        the regexp matches, again a SUPERSET of those with stored rows
        in the range. A store may read their rows by point lookup in
        place of listing and filtering the range; ignoring it is
        always correct, and the rows that come back are the same."""
        for cells in self.scan(table, start, stop, family=family,
                               key_regexp=key_regexp):
            yield cells[0].key, [(c.qualifier, c.value) for c in cells]

    def atomic_increment(self, table: str, key: bytes, family: bytes,
                         qualifier: bytes, amount: int = 1) -> int:
        raise NotImplementedError

    def compare_and_set(self, table: str, key: bytes, family: bytes,
                        qualifier: bytes, expected: bytes | None,
                        value: bytes) -> bool:
        raise NotImplementedError

    def flush(self) -> None:
        raise NotImplementedError

    def wal_barrier(self, ticket: int | None = None) -> None:
        """Wait for the WAL group-commit flush covering everything
        appended so far (see MemKVStore). Default: no-op — stores
        without group commit are already durable at return from every
        mutation."""

    def ensure_table(self, table: str) -> None:
        raise NotImplementedError


def _merge_unique(a: list[bytes], b: list[bytes]) -> list[bytes]:
    """Merge two sorted unique lists into one, dropping cross-duplicates
    (a key deleted and re-inserted can appear in both runs)."""
    out: list[bytes] = []
    i = j = 0
    na, nb = len(a), len(b)
    while i < na and j < nb:
        ka, kb = a[i], b[j]
        if ka < kb:
            out.append(ka)
            i += 1
        elif kb < ka:
            out.append(kb)
            j += 1
        else:
            out.append(ka)
            i += 1
            j += 1
    out.extend(a[i:])
    out.extend(b[j:])
    return out


class _TiersMoved(FileNotFoundError):
    """A replica's load saw the writer rotate or commit between its
    reads of the manifest, <wal>.old and the WAL."""


class _Table:
    """Row storage + an incremental sorted key index.

    The index is a sqrt-decomposition over two sorted runs: ``base``
    (large, rebuilt rarely) and ``delta`` (small, absorbing recent
    inserts), plus an unsorted ``pending`` set for brand-new keys.
    Inserts are O(1) (set add); a scan absorbs pending into delta
    (O(P log P + D)) and folds delta into base only when delta outgrows
    ~sqrt(base) — so interleaved put/scan traffic no longer pays the old
    O(rows log rows) full re-sort per scan (the dashboard-poll +
    continuous-ingest hot pattern; fills the role of the LSM memtable
    index in front of HBase's store files, reference
    TsdbQuery.java:240-285 scan hot loop). Runs may carry stale (deleted)
    keys; readers filter on ``k in rows`` and a purge rewrites the runs
    when stale entries dominate.
    """

    __slots__ = ("rows", "base", "delta", "pending", "stale", "row_tombs",
                 "tombs", "dirty", "touch")

    def __init__(self) -> None:
        # Cell value None = tombstone masking a spilled sstable cell.
        self.rows: dict[bytes, dict[tuple[bytes, bytes], bytes | None]] = {}
        self.base: list[bytes] = []
        self.delta: list[bytes] = []
        self.pending: set[bytes] = set()
        self.stale = 0  # deleted keys still present in base/delta
        self.row_tombs: set[bytes] = set()  # whole-row masks over the sstable
        # Count of cell tombstones ever written into rows (checkpoint
        # uses it to pick the fast memtable-only spill: a tier with no
        # tombstones cannot mask lower-generation cells, so spilling it
        # as a new generation needs no merge).
        self.tombs = 0
        # Incremental dirty-base index: base-time -> refcount of keys
        # (rows + row_tombs entries, counted separately — a key can be
        # in both) whose base-time bytes name it. Maintained O(1) per
        # row insert/remove so ``dirty_bases`` never sweeps the key
        # list (the planner used to re-sweep the whole memtable under
        # this lock on every rollup-eligible query).
        self.dirty: dict[int, int] = {}
        # Touch sequence per base: the store mutation_seq of the last
        # row-create/remove transition. A create-then-full-delete nets
        # the refcount back to zero — the base reads CLEAN again — but
        # a fragment scanned DURING that window may hold the transient
        # row; the touch value outlives the refcount so such fragments
        # can never validate (fragment-cache contract,
        # MemKVStore.chunk_state).
        self.touch: dict[int, int] = {}

    def note_insert(self, key: bytes) -> None:
        self.pending.add(key)

    def note_delete(self) -> None:
        self.stale += 1

    def dirty_add(self, key: bytes, seq: int) -> None:
        if len(key) >= _BASE_HI:
            b = int.from_bytes(key[_BASE_LO:_BASE_HI], "big")
            d = self.dirty
            d[b] = d.get(b, 0) + 1
            self.touch[b] = seq

    def dirty_sub(self, key: bytes, seq: int) -> None:
        if len(key) >= _BASE_HI:
            b = int.from_bytes(key[_BASE_LO:_BASE_HI], "big")
            d = self.dirty
            n = d.get(b, 0) - 1
            if n <= 0:
                d.pop(b, None)
            else:
                d[b] = n
            self.touch[b] = seq

    def rebuild_dirty(self, seq: int) -> None:
        """Recompute the dirty-base index from scratch (the thaw path,
        where refcount bookkeeping through the merge-back would be
        error-prone for an exceptional branch). Every involved base's
        touch jumps to ``seq`` — conservative invalidation of any
        fragment built across the thaw."""
        d: dict[int, int] = {}
        for ks in (self.rows, self.row_tombs):
            for k in ks:
                if len(k) >= _BASE_HI:
                    b = int.from_bytes(k[_BASE_LO:_BASE_HI], "big")
                    d[b] = d.get(b, 0) + 1
        for b in d:
            self.touch[b] = seq
        self.dirty = d

    def _absorb(self) -> None:
        """Fold pending inserts into delta; compact when thresholds hit.
        Caller holds the store lock."""
        if self.pending:
            new = sorted(self.pending)
            self.pending.clear()
            self.delta = _merge_unique(self.delta, new) if self.delta \
                else new
        if len(self.delta) ** 2 > max(len(self.base), 64):
            self.base = _merge_unique(self.base, self.delta)
            self.delta = []
        if self.stale * 2 > len(self.base) + len(self.delta):
            rows = self.rows
            self.base = [k for k in self.base if k in rows]
            self.delta = [k for k in self.delta if k in rows]
            self.stale = 0

    def range_keys(self, start: bytes, stop: bytes | None) -> list[bytes]:
        """Sorted live keys in [start, stop); stop falsy = to the end.
        Merge-iterates the two runs, skipping stale keys and
        cross-duplicates. Caller holds the store lock."""
        self._absorb()
        a, b = self.base, self.delta
        i, j = bisect_left(a, start), bisect_left(b, start)
        ahi = bisect_left(a, stop) if stop else len(a)
        bhi = bisect_left(b, stop) if stop else len(b)
        rows = self.rows
        out: list[bytes] = []
        while i < ahi and j < bhi:
            ka, kb = a[i], b[j]
            if ka < kb:
                k = ka
                i += 1
            elif kb < ka:
                k = kb
                j += 1
            else:
                k = ka
                i += 1
                j += 1
            if k in rows:
                out.append(k)
        for k in a[i:ahi]:
            if k in rows:
                out.append(k)
        for k in b[j:bhi]:
            if k in rows:
                out.append(k)
        return out

    def range_count(self, start: bytes, stop: bytes | None) -> int:
        """Upper bound on len(range_keys(start, stop)) from the same
        bisects, listing nothing (stale keys, cross-duplicates and the
        unsorted pending inserts all count). Caller holds the store
        lock."""
        n = len(self.pending)
        for run in (self.base, self.delta):
            hi = bisect_left(run, stop) if stop else len(run)
            n += hi - bisect_left(run, start)
        return n


# WAL opcodes
_OP_PUT = 1
_OP_DELETE = 2
_OP_DELETE_ROW = 3
_OP_PUT_BATCH = 4   # one record for a whole put_many batch
# WAL segment epoch header (cluster/epoch.py): a cluster-mode writer
# begins every WAL segment it opens with its epoch, and replay refuses
# any segment whose header epoch is LOWER than one already seen — the
# on-disk artifact of a split brain (a deposed writer's records landing
# after a newer writer's) is cut at the fence line, never applied.
_OP_EPOCH = 5


class MemKVStore(KVStore):
    """In-memory ordered KV with optional WAL persistence.

    Thread-safe: a single lock guards all mutation (ingest is batched above
    this layer, so lock traffic is per-batch, not per-point).
    """

    # Sabotage gate for the crash matrix (fault/harness.py --bug
    # ack-before-fsync): True makes _wal_barrier return immediately,
    # acking group-commit writes before their covering fsync — the
    # exact regression the kv.wal.group.* matrix rows must catch.
    _ACK_BEFORE_FSYNC = False

    def __init__(self, wal_path: str | None = None,
                 throttle_rows: int | None = None,
                 fsync: bool = False, read_only: bool = False,
                 max_generations: int | None = None,
                 writer_epoch: int | None = None,
                 epoch_guard=None) -> None:
        """``max_generations`` overrides the sstable generation cap
        (default ``_MAX_GENERATIONS``); the sharded store staggers it
        per shard so size-tiered collapses don't fire on the same
        checkpoint across shards.

        ``writer_epoch`` (cluster mode, cluster/epoch.py) stamps this
        writer's ownership epoch into every WAL segment it opens and
        arms the replay-side fence; ``epoch_guard`` (an
        ``EpochGuard``) is checked from every mutation entry point and
        from ``checkpoint()`` so a deposed writer raises
        ``FencedWriterError`` instead of split-braining the store.
        Both default off — a non-cluster store's WAL bytes and hot
        path are unchanged.

        ``read_only=True`` opens another daemon's store WITHOUT the
        single-writer lock: a replica that serves reads over the same
        WAL + sstable generations while the writer keeps ingesting —
        the reference's N-TSDs-over-one-shared-store deployment shape
        (reference README:8-17). Replicas never truncate torn WAL
        tails (the writer may be mid-append), never delete
        manifest-stray generation files, and refuse every mutation
        with ReadOnlyStoreError; ``refresh()`` catches the replica up
        to the writer's latest durable state."""
        self._tables: dict[str, _Table] = {}
        self._lock = threading.RLock()
        if max_generations is not None:
            if max_generations < 2:
                raise ValueError(
                    f"max_generations must be >= 2, got {max_generations}")
            self._MAX_GENERATIONS = max_generations
        self.throttle_rows = throttle_rows
        self._fsync = fsync
        self._wal_path = wal_path
        self.read_only = read_only
        # Cluster write tier (cluster/): the epoch this writer owns
        # (None = non-cluster store, no headers, no fence), the
        # mutation-path guard, the highest segment-header epoch the
        # replay stream has produced so far, and the bytes replay
        # refused past a fence line (zombie segments).
        self.writer_epoch = writer_epoch
        self.epoch_guard = epoch_guard
        self._replay_epoch = 0
        self.fenced_bytes_refused = 0
        # Count of replica full rebuilds (each corresponds to a writer
        # checkpoint/rotation); TSDB's refresh timer keys sketch
        # snapshot reloads off it.
        self.rebuilds = 0
        # Replica replay position: {"wal": (inode, replayed bytes),
        # "old": (inode, size) | None} — refresh() replays just the
        # WAL suffix when the writer has only appended, and rebuilds
        # only when the WAL rotated, the manifest changed, or the
        # <wal>.old file appeared/changed (NOT on every poll while a
        # writer's long merge keeps .old on disk).
        self._ro_state: dict | None = None
        self._wal: io.BufferedWriter | None = None
        # Spill tier: a LIST of sstable generations, OLDEST FIRST. A
        # checkpoint normally spills just the frozen memtable as a new
        # generation (O(new rows), not O(total) — full rewrites grew
        # linearly: 28s at 25M points, 114s at 75M); reads overlay
        # generations in order. A full merge (collapse to one
        # generation) runs only when the frozen tier holds tombstones
        # (which must mask lower-generation cells) or the generation
        # count hits _MAX_GENERATIONS.
        self._ssts: list[SSTable] = []
        self._sst_path = wal_path + ".sst" if wal_path else None
        # Write-side sstable codec (Config.sstable_codec): "none"
        # spills the WRITE_FORMAT legacy layout; "tsst4" spills
        # compressed columnar blocks. Read-side is self-describing per
        # file, so mixed-format generation sets are first-class and
        # flipping this only affects FUTURE spills (compaction
        # re-encodes as generations merge).
        self.sstable_codec = "none"
        # WAL group commit (Config.wal_group_ms, set externally like
        # sstable_codec): > 0 defers the per-append flush+fsync into a
        # leader-elected group flush. Append paths bump _grp_written
        # (a ticket counter) UNDER the store lock; ack paths call
        # _wal_barrier(ticket) AFTER releasing it and park on
        # _grp_cond until _grp_flushed covers their ticket. Lock
        # order is store lock -> _grp_cond everywhere.
        self._wal_group_ms = 0.0
        self._grp_cond = threading.Condition()
        self._grp_written = 0     # tickets issued (appends recorded)
        self._grp_flushed = 0     # tickets covered by an fsync
        self._grp_leader = False  # a leader is collecting/flushing
        self._grp_file_epoch = 0  # bumped per WAL rotation
        # Last byte offset covered by a group fsync — bounds the torn
        # span the kv.wal.group.fsync faultpoint may cut (never into
        # previously durable bytes).
        self._grp_synced_pos = 0
        # Flush failures SWALLOWED on put_many's exceptional exit (the
        # in-flight throttle error wins) — the one case where a flush
        # failure cannot propagate to the caller. Ordinary flush
        # failures raise loudly and are not counted here; nonzero means
        # acknowledged cells whose WAL records may not have reached the
        # OS with no exception having told anyone.
        self.wal_swallowed_flush_errors = 0
        # Monotonic mutation counter (bumped per mutating CALL, not per
        # cell, plus checkpoint tier transitions): consumers that derive
        # state from memtable contents (the rollup tier's dirty-window
        # set) key their caches on it — unchanged seq means the
        # memtable cannot have changed.
        self.mutation_seq = 0
        # Rollup-tier hook: when set, checkpoint() records the row keys
        # of every spilled frozen tier (including row tombstones) so
        # the materialized-summary fold covers exactly what left the
        # memtable; take_spill_keys() drains the record.
        self.record_spill_keys = False
        self._last_spill_keys: dict[str, list[bytes]] = {}
        # Rollup-tier hook: called as fn(table, key) on every delete /
        # delete_row so the incremental-fold accumulators (rollup/
        # delta.py) learn when a row's point set changed out-of-band;
        # None when no tier is listening.
        self.delete_hook = None
        # Dirty-base refcounts of the UNDRAINED spill record (the
        # frozen tier's dirty index, carried over at phase 3 and summed
        # across checkpoints like _last_spill_keys): spilled keys count
        # as dirty until the rollup fold drains them, so dirty_bases
        # never has to derive bases from the (possibly huge) key list.
        self._spill_dirty: dict[str, dict[int, int]] = {}
        # The fragment cache's invalidation spine: per (table, base),
        # the mutation_seq of the last row-create/remove transition
        # that touched it — folded here from each tier's ``touch`` map
        # when the tier retires (phase-3 drop, empty-checkpoint drop,
        # thaw), so the signal outlives the memtable generation that
        # produced it. A fragment built at store seq E over a CLEAN
        # base range is still exact iff no base in the range carries a
        # stamp > E and E >= _stamp_floor: rows only enter or leave
        # the visible dataset through stamped memtable transitions
        # (puts, deletes, tombstones), every checkpoint merely
        # relocates them between tiers, and a replica rebuild — where
        # what changed is unknown — jumps the floor instead.
        self._base_stamps: dict[str, dict[int, int]] = {}
        self._stamp_floor = 0
        # Lazy snapshots for range queries (rebuilt when mutation_seq
        # moves): table -> (seq, sorted bases, aligned stamps).
        self._stamps_snap: dict[str, tuple[int, np.ndarray,
                                           np.ndarray]] = {}
        self._dirty_snap: dict[str, tuple[int, np.ndarray]] = {}
        # Generations skipped by the series-bloom prefilter (scan_raw
        # with a series_hint), exported as bloom.files_skipped.
        self.bloom_files_skipped = 0
        # Per-generation bisects skipped by the point-get bloom probe
        # (_lower_tier_has), exported as bloom.point_skips.
        self.bloom_point_skips = 0
        # Immutable middle tier while a checkpoint merge is in flight.
        self._frozen: dict[str, _Table] | None = None
        self._lockfd: int | None = None
        if wal_path and not read_only:
            # Create the WAL's parent directory so a fresh --wal path
            # works without operator mkdir (same courtesy as the /q
            # cache dir).
            parent = os.path.dirname(os.path.abspath(wal_path))
            os.makedirs(parent, exist_ok=True)
            # Advisory single-writer lock, held for the store's
            # lifetime and acquired BEFORE any recovery work touches
            # disk: _generation_paths deletes any generation file the
            # manifest doesn't name, so a second opener racing a
            # writer between its generation rename and manifest write
            # would unlink the writer's live spill. A separate .lock
            # file (not the WAL itself) because checkpoint
            # rotates/reopens the WAL, which would drop a lock held on
            # its fd.
            self._lockfd = os.open(wal_path + ".lock",
                                   os.O_CREAT | os.O_RDWR, 0o644)
            try:
                fcntl.flock(self._lockfd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                os.close(self._lockfd)
                self._lockfd = None
                raise RuntimeError(
                    f"WAL path {wal_path!r} is locked by another "
                    f"MemKVStore (single-writer store; remove "
                    f"{wal_path}.lock only if the owner is dead)")
        try:
            if read_only:
                self._open_tiers_retrying(wal_path)
            else:
                self._open_tiers(wal_path)
        except BaseException:
            # Recovery failed after the flock was acquired (corrupt
            # generation file, WAL replay error): release the lock or
            # an in-process repair-and-retry would be refused with a
            # misleading "locked by another store" forever.
            for sst in self._ssts:
                sst.close()
            self._ssts = []
            if self._lockfd is not None:
                os.close(self._lockfd)
                self._lockfd = None
            raise

    # A load beside a writer that checkpoints back to back is redone
    # whenever a rotation or a commit fell inside it (_TiersMoved).
    _OPEN_TRIES = 32

    def _open_tiers_retrying(self, wal_path: str | None) -> None:
        """_open_tiers for replicas, retrying on FileNotFoundError: a
        live writer's merge can unlink a dropped generation between
        the replica's manifest read and the file open (found by the
        replica-vs-writer stress test), or rotate and commit between
        the reads of one load (_TiersMoved). The manifest converges,
        so a bounded re-read wins the race; skipping the missing file
        instead would silently drop its rows."""
        for _ in range(self._OPEN_TRIES):
            for sst in self._ssts:
                sst.close()
            self._tables = {}
            self._ssts = []
            try:
                self._open_tiers(wal_path)
                return
            except FileNotFoundError:
                continue
        raise FileNotFoundError(
            f"generation set for {wal_path!r} kept changing mid-open "
            f"(writer merging continuously?); gave up after "
            f"{self._OPEN_TRIES} tries")

    def _open_tiers(self, wal_path: str | None) -> None:
        """Load sstable generations, replay the WAL(s), open for append
        (the recovery tail of __init__; caller owns lock-fd cleanup on
        failure)."""
        self._replay_epoch = 0
        gen_paths = self._generation_paths() if self._sst_path else []
        old_seen = self._stat_old() if wal_path and self.read_only else None
        if self._sst_path:
            for path in gen_paths:
                sst = SSTable(path)
                self._ssts.append(sst)
                for name in sst.tables():
                    self._table(name)
        if wal_path:
            # A leftover <wal>.old means a crash interrupted a checkpoint:
            # replay it first (records older than everything in the WAL).
            old_path = wal_path + ".old"
            if os.path.exists(old_path):
                old_valid = self._replay(old_path)
                if old_valid < os.path.getsize(old_path) \
                        and not self.read_only:
                    # Torn tail: truncate, or a later checkpoint would
                    # append live records after the garbage where replay
                    # can never reach them. (A replica never truncates:
                    # the "torn" tail may be the writer mid-append.)
                    with open(old_path, "r+b") as f:
                        f.truncate(old_valid)
            valid_bytes = 0
            ino = -1
            if os.path.exists(wal_path):
                ino = os.stat(wal_path).st_ino
                valid_bytes = self._replay(wal_path)
                if valid_bytes < os.path.getsize(wal_path) \
                        and not self.read_only:
                    # Torn record at the tail (crash mid-write): truncate it
                    # away so appends continue from the last valid boundary —
                    # otherwise the next replay would stop at the garbage and
                    # silently drop everything written after it.
                    with open(wal_path, "r+b") as f:
                        f.truncate(valid_bytes)
            if self.read_only:
                # The manifest, <wal>.old and the WAL were read one
                # after another beside a live writer. A rotation after
                # the .old check leaves a view without the records
                # that just moved into .old; a commit after the
                # manifest read leaves one without the generation that
                # took .old's records in. Either shows as a key going
                # backwards (the replica-vs-writer stress test, once in
                # ~20 runs on a loaded machine). Both change the
                # manifest or .old, so read them again and start over.
                if self._stat_old() != old_seen or (
                        self._sst_path
                        and self._generation_paths() != gen_paths):
                    raise _TiersMoved(wal_path)
                self._ro_state = {"wal": (ino, valid_bytes),
                                  "old": old_seen}
            else:
                self._wal = open(wal_path, "ab")
                self._stamp_epoch_header()

    def _stat_old(self) -> "tuple[int, int] | None":
        try:
            st = os.stat(self._wal_path + ".old")
            return (st.st_ino, st.st_size)
        except OSError:
            return None

    def refresh(self) -> bool:
        """Catch a read-only replica up to the writer's current durable
        state. Returns True when anything changed.

        When the WAL is the same file and has only grown, just the
        suffix replays (cheap steady-state poll). A rotated WAL or a
        changed manifest (the writer checkpointed) triggers a full
        rebuild — which is exactly crash recovery, so it is correct in
        ANY in-flight writer state: mid-checkpoint the replica sees the
        old manifest + <wal>.old + fresh WAL, and replaying .old then
        the WAL over the manifest generations reproduces the data."""
        if not self.read_only:
            raise ValueError("refresh() is for read-only stores")
        if not self._wal_path:
            return False
        # raise/ioerror here simulate a poll hitting writer churn or a
        # flaky volume: the replica must keep serving its coherent
        # pre-refresh view (delay widens the rebuild-vs-writer races).
        _fault("replica.refresh", self._wal_path)
        with self._lock:
            man_now = self._generation_paths()
            if [s.path for s in self._ssts] != man_now:
                self._rebuild_locked()
                return True
            state = self._ro_state or {"wal": (-1, 0), "old": None}
            if self._stat_old() != state["old"]:
                # <wal>.old appeared/changed: a writer checkpoint is in
                # flight (or a new crash remnant) — its records precede
                # the current WAL, so a rebuild is the only correct
                # catch-up. Recording its (inode, size) means a LONG
                # merge (minutes at 1B scale) costs one rebuild, not
                # one per poll.
                self._rebuild_locked()
                return True
            try:
                f = open(self._wal_path, "rb")
            except OSError:
                return False
            with f:
                # fstat on the OPEN fd: a writer rotation between a
                # path-stat and the open would otherwise let the
                # replay seek to the old file's offset inside the NEW
                # file and misparse garbage as records (the WAL frame
                # has no checksum).
                st = os.fstat(f.fileno())
                ino, off = state["wal"]
                if st.st_ino != ino or st.st_size < off:
                    self._rebuild_locked()
                    return True
                if st.st_size == off:
                    return False
                valid = self._replay_file(f, start=off)
            self._ro_state = {"wal": (ino, valid),
                              "old": state["old"]}
            if valid > off:
                # The replayed suffix mutated the memtable outside the
                # put/delete entry points: consumers keying caches on
                # mutation_seq must see it move.
                self.mutation_seq += 1
            return valid > off

    def _rebuild_locked(self) -> None:
        """Full replica reload: fresh tables, current generations,
        .old + WAL replay (the crash-recovery path, minus truncation).
        Caller holds the lock. Open sstable handles for dropped
        generations close afterwards — Linux keeps unlinked files
        readable until the fd closes, so readers racing a writer's
        full merge never see missing data."""
        old_ssts = self._ssts
        old_tables = self._tables
        old_state = self._ro_state
        _fault("replica.rebuild", self._wal_path)
        self._ssts = []
        self._ro_state = None
        try:
            self._open_tiers_retrying(self._wal_path)
        except BaseException:
            # Keep serving the STALE-but-consistent pre-rebuild view
            # (and don't leak its fds): half-loaded tables would serve
            # torn reads to a poller that treats the failure as
            # transient.
            for sst in self._ssts:
                sst.close()
            self._ssts = old_ssts
            self._tables = old_tables
            self._ro_state = old_state
            raise
        self.rebuilds += 1
        self.mutation_seq += 1
        # A rebuild replaced the generation set wholesale; what changed
        # inside it is unknown, so the stamp floor jumps and every
        # fragment cached against an earlier seq is invalid.
        self._stamp_floor = self.mutation_seq
        self._base_stamps = {}
        self._stamps_snap = {}
        self._dirty_snap = {}
        for sst in old_ssts:
            sst.close()

    _MAX_GENERATIONS = 8

    def _generation_paths(self) -> list[str]:
        """Live spill generations, oldest first. The manifest (written
        atomically on every checkpoint) is the source of truth — stray
        generation files it does not name (crash leftovers between a
        full-merge swap and the old-file unlinks) are deleted here,
        because loading them would resurrect cells a merge already
        dropped. No manifest = legacy layout: the single ``<wal>.sst``."""
        man = self._sst_path + ".manifest"
        d = os.path.dirname(os.path.abspath(self._sst_path))
        if not os.path.exists(man):
            return [self._sst_path] if os.path.exists(self._sst_path) \
                else []
        import json as _json
        with open(man) as f:
            names = _json.load(f)
        live = [os.path.join(d, fn) for fn in names]
        if self.read_only:
            # Replicas must never delete (a "stray" may be the live
            # writer's generation mid-rename) — and must NOT filter on
            # existence either: a writer merge can unlink a manifest
            # generation between our manifest read and this point, and
            # silently dropping it would serve reads missing all its
            # rows. Returning the path unfiltered makes the SSTable
            # open raise FileNotFoundError, which the replica's retry
            # turns into a manifest re-read.
            return live
        liveset = set(names)
        base = os.path.basename(self._sst_path)
        for fn in os.listdir(d):
            if (fn == base or fn.startswith(base + ".g")) \
                    and fn not in liveset \
                    and not fn.endswith(".tmp") \
                    and not fn.endswith(".manifest"):
                try:
                    os.unlink(os.path.join(d, fn))
                except OSError:
                    pass
        return [p for p in live if os.path.exists(p)]

    def _write_manifest(self, paths: list[str]) -> None:
        """Atomically record the live generation set (tmp + rename +
        directory fsync, same durability contract as write_sstable)."""
        import json as _json
        man = self._sst_path + ".manifest"
        tmp = man + ".tmp"
        with open(tmp, "w") as f:
            _json.dump([os.path.basename(p) for p in paths], f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, man)
        dfd = os.open(os.path.dirname(os.path.abspath(man)), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def _next_generation_path(self) -> str:
        used = set()
        d = os.path.dirname(os.path.abspath(self._sst_path))
        prefix = os.path.basename(self._sst_path) + ".g"
        for fn in os.listdir(d):
            if fn.startswith(prefix) and not fn.endswith(".tmp") \
                    and not fn.endswith(".manifest"):
                try:
                    used.add(int(fn[len(prefix):]))
                except ValueError:
                    continue
        n = 1
        while n in used:
            n += 1
        return self._sst_path + f".g{n}"

    # -- table helpers ----------------------------------------------------

    def _table(self, name: str) -> _Table:
        t = self._tables.get(name)
        if t is None:
            t = self._tables[name] = _Table()
        return t

    def ensure_table(self, table: str) -> None:
        with self._lock:
            self._table(table)

    def _check_writable(self) -> None:
        if self.read_only:
            raise ReadOnlyStoreError(
                f"store on {self._wal_path!r} is a read-only replica")
        if self.epoch_guard is not None:
            # The zombie fence (cluster/epoch.py): raises
            # FencedWriterError once a promotion has bumped the
            # persisted epoch past ours. Stat-cached — nothing
            # measurable on the batched ingest path.
            self.epoch_guard.check()

    def memtable_keys(self, table: str) -> list[bytes]:
        """Row keys in the live memtable only (excludes spilled tiers).
        After crash recovery this is exactly the WAL-replayed set — what
        a checkpoint-snapshot consumer (TSDB sketch rebuild) must re-fold
        on top of its snapshot."""
        with self._lock:
            return list(self._table(table).rows)

    def memtable_row_counts(self, table: str) -> list[int]:
        """Live-memtable row count, one element per shard (one, here) —
        the /stats per-shard memtable gauge."""
        with self._lock:
            return [len(self._table(table).rows)]

    def sstable_format_bytes(self) -> dict[int, int]:
        """On-disk bytes of the live generation set, keyed by sstable
        format version (1-4) — the /stats ``sstable.bytes{format=}``
        gauge and fsck's format-mix report."""
        out: dict[int, int] = {}
        with self._lock:
            gens = list(self._ssts)
        for sst in gens:
            try:
                sz = os.path.getsize(sst.path)
            except OSError:
                continue
            out[sst.format] = out.get(sst.format, 0) + sz
        return out

    def compress_stats(self) -> tuple[int, int]:
        """(uncompressed_record_bytes, stored_record_bytes) summed over
        the v4 generations — ``compress.ratio`` = raw / stored. (0, 0)
        when no generation is compressed."""
        raw = enc = 0
        with self._lock:
            gens = list(self._ssts)
        for sst in gens:
            cs = sst.codec_stats()
            if cs is not None:
                raw += cs[0]
                enc += cs[1]
        return raw, enc

    def encoded_range(self, table: str, start: bytes,
                      stop: bytes | None):
        """The fused decode-aggregate path's source check: when every
        generation holding keys in [start, stop) is format v4, returns
        [(sstable, lo_idx, hi_idx)] ordered by first key. Returns None
        whenever serving the range off raw blocks could diverge from a
        scan: a frozen mid-checkpoint tier, live row tombstones, or a
        non-v4 generation in range. Two residual overlay risks are the
        CALLER's checks: memtable-resident rows (executor chunk_state:
        any dirty base in range declines the fused plan) and duplicate
        keys ACROSS generations (compress/fused.gather verifies the
        copies' qualifier-delta ranges are disjoint — the mid-hour
        checkpoint-boundary straddle, where the overlay is a pure
        union — and declines otherwise)."""
        with self._lock:
            if self._frozen is not None:
                return None
            t = self._tables.get(table)
            if t is not None and t.row_tombs:
                return None
            gens = list(self._ssts)
        spans = []
        for g in gens:
            idx = g._index.get(table)
            if not idx or not idx[0]:
                continue
            keys, _ = idx
            lo = bisect_left(keys, start)
            hi = bisect_left(keys, stop) if stop else len(keys)
            if lo == hi:
                continue
            if g.format != 4:
                return None
            spans.append((g, lo, hi, keys[lo]))
        spans.sort(key=lambda s: s[3])
        return [(g, lo, hi) for g, lo, hi, _ in spans]

    def pending_keys(self, table: str) -> list[bytes]:
        """Row keys (and row tombstones) NOT yet covered by the rollup
        fold: the live memtable, a frozen mid-checkpoint tier, and the
        UNDRAINED spilled-key record. This is the rollup planner's
        dirty-window source. Spilled keys count as pending until the
        fold drains them (take_spill_keys) precisely so no instant
        exists where a spilled-but-unfolded window is in neither this
        set nor the tier's in-flight set — the fold marks its windows
        in flight BEFORE draining (rollup/tier.py fold_after_spill)."""
        with self._lock:
            t = self._table(table)
            out = list(t.rows)
            out.extend(t.row_tombs)
            if self._frozen is not None:
                ft = self._frozen.get(table)
                if ft is not None:
                    out.extend(ft.rows)
                    out.extend(ft.row_tombs)
            out.extend(self._last_spill_keys.get(table, ()))
            return out

    def peek_spill_keys(self) -> dict[str, list[bytes]]:
        """Non-draining copy of the spilled-key record: the rollup fold
        reads it to mark windows in flight while their keys still read
        as pending, THEN drains with take_spill_keys."""
        with self._lock:
            return {name: list(ks)
                    for name, ks in self._last_spill_keys.items()}

    def take_spill_keys(self) -> dict[str, list[bytes]]:
        """Drain the spilled-key record (see record_spill_keys)."""
        with self._lock:
            out, self._last_spill_keys = self._last_spill_keys, {}
            self._spill_dirty = {}
            self.mutation_seq += 1  # the dirty-base set just shrank
            return out

    @property
    def spilled(self) -> bool:
        """Whether any sstable generation exists (data outside the
        WAL-replayable memtable)."""
        return bool(self._ssts)

    @property
    def mutation_seqs(self) -> tuple[int, ...]:
        """Per-shard mutation sequence vector (a single store is one
        shard). The sharded store's summed ``mutation_seq`` makes one
        put anywhere invalidate everything derived from it; consumers
        that can revalidate per shard key on this instead."""
        return (self.mutation_seq,)

    def dirty_bases(self, table: str) -> np.ndarray:
        """Sorted unique base times whose rows are NOT fully covered by
        the immutable sstable tiers: live memtable rows + row
        tombstones, the frozen mid-checkpoint tier, and the undrained
        spill record — maintained incrementally (O(1) amortized per
        mutation, see _Table.dirty) so deriving it never sweeps the
        key list. Cached per mutation_seq; the rollup planner's
        dirty-window set and the fragment cache's bypass test both
        read it."""
        with self._lock:
            snap = self._dirty_snap.get(table)
            if snap is not None and snap[0] == self.mutation_seq:
                return snap[1]
            bases = set(self._table(table).dirty)
            if self._frozen is not None:
                ft = self._frozen.get(table)
                if ft is not None:
                    bases.update(ft.dirty)
            sd = self._spill_dirty.get(table)
            if sd:
                bases.update(sd)
            arr = np.fromiter(bases, np.int64, len(bases))
            arr.sort()
            self._dirty_snap[table] = (self.mutation_seq, arr)
            return arr

    def chunk_state(self, table: str, lo: int, hi: int,
                    ) -> tuple[tuple[int, ...], tuple[int, ...],
                               tuple[int, ...], bool]:
        """Fragment-cache validation state for base range [lo, hi):
        ``(seqs, floors, stamps, dirty)`` — one element per shard
        (one, here). A fragment tagged with seq E over this range is
        still exact iff the range is clean (not ``dirty``),
        E >= floor, and no base in the range carries a transition
        stamp > E (``stamps`` is the range's newest stamp across the
        store-level map and every live tier's touch map). Rows only
        enter or leave the visible dataset through stamped memtable
        transitions, so an unchanged stamp range means unchanged
        content — checkpoints merely relocate rows between tiers."""
        d = self.dirty_bases(table)
        dirty = bool(len(d)) and \
            int(np.searchsorted(d, lo)) < int(np.searchsorted(d, hi))
        with self._lock:
            seq = self.mutation_seq
            snap = self._stamps_snap.get(table)
            if snap is None or snap[0] != seq:
                m = dict(self._base_stamps.get(table, {}))
                tiers = [self._table(table)]
                if self._frozen is not None:
                    ft = self._frozen.get(table)
                    if ft is not None:
                        tiers.append(ft)
                for t in tiers:
                    for b, v in t.touch.items():
                        if m.get(b, -1) < v:
                            m[b] = v
                bases = np.fromiter(m.keys(), np.int64, len(m))
                stamps = np.fromiter(m.values(), np.int64, len(m))
                order = np.argsort(bases)
                snap = (seq, bases[order], stamps[order])
                self._stamps_snap[table] = snap
            _, bases, stamps = snap
            a = int(np.searchsorted(bases, lo))
            b = int(np.searchsorted(bases, hi))
            stamp = int(stamps[a:b].max()) if b > a else 0
            return ((seq,), (self._stamp_floor,), (stamp,), dirty)

    def memtable_cells(self, table: str, key: bytes,
                       family: bytes | None = None) -> list[Cell]:
        """Live-memtable cells of one row, WITHOUT merging spilled tiers
        (tombstones excluded). The recovery re-fold reads rows through
        this so cells already covered by the sketch snapshot (sstable
        tier) are not folded twice."""
        with self._lock:
            row = self._table(table).rows.get(key)
            if not row:
                return []
            return [Cell(key, f, q, v) for (f, q), v in row.items()
                    if v is not None and (family is None or f == family)]

    def row_count(self, table: str) -> int:
        with self._lock:
            t = self._table(table)
            keys = set(t.rows)
            ft = self._frozen.get(table) if self._frozen else None
            if ft is not None:
                keys |= set(ft.rows)
            for sst in self._ssts:
                keys.update(sst.scan_keys(table, b"", None))
            return sum(1 for k in keys if self._merged_row(table, k))

    def has_row(self, table: str, key: bytes) -> bool:
        with self._lock:
            return self._has_row_locked(table, key)

    def _has_row_locked(self, table: str, key: bytes) -> bool:
        row = self._table(table).rows.get(key)
        if row:
            # Tombstones (None cells) only exist once a lower tier
            # does; the pure-memtable hot ingest path stays O(1).
            if not self._ssts and self._frozen is None:
                return True
            if any(v is not None for v in row.values()):
                return True
        return self._merged_row(table, key) is not None

    def cell_count(self, table: str, key: bytes) -> int:
        with self._lock:
            row = self._merged_row(table, key)
            return len(row) if row else 0

    def _merged_row(self, table: str,
                    key: bytes) -> dict[tuple[bytes, bytes], bytes] | None:
        """Lower tiers (sstable, then frozen memtable) overlaid with the
        live memtable's cells/tombstones. Caller holds the lock."""
        t = self._table(table)
        if not self._ssts and self._frozen is None:
            # No lower tiers => no tombstones possible; serve the row
            # as-is (the default-config hot path allocates nothing).
            return t.rows.get(key) or None
        ft = self._frozen.get(table) if self._frozen else None
        merged: dict[tuple[bytes, bytes], bytes] = {}
        sst_masked = key in t.row_tombs or (
            ft is not None and key in ft.row_tombs)
        if not sst_masked:
            # Overlay generations oldest -> newest (generations never
            # hold tombstones — a tombstoned frozen tier forces a full
            # merge — so plain dict overlay is the whole story).
            for sst in self._ssts:
                cells = sst.get(table, key)
                if cells:
                    for f, q, v in cells:
                        merged[(f, q)] = v
        if ft is not None and key not in t.row_tombs:
            row = ft.rows.get(key)
            if row:
                for ck, v in row.items():
                    if v is None:
                        merged.pop(ck, None)
                    else:
                        merged[ck] = v
        row = t.rows.get(key)
        if row:
            for ck, v in row.items():
                if v is None:
                    merged.pop(ck, None)
                else:
                    merged[ck] = v
        return merged or None

    def _lower_tier_has(self, t: _Table, table: str, key: bytes) -> bool:
        """Does any tier below the live memtable hold this key? (Decides
        whether a delete must leave tombstones.)

        Consults each generation's series bloom BEFORE the key bisect:
        generations whose bloom excludes the key's series identity
        cannot hold the key (blooms cover every indexed key — fsck
        audits the no-false-negative invariant), so point deletes over
        high-generation-count stores skip most bisects. The probe hash
        is the same crc32 chain the bloom writer uses, so present keys
        always pass; a stale bit (tombstoned key) only costs one
        needless bisect."""
        ft = self._frozen.get(table) if self._frozen else None
        if ft is not None and (key in ft.rows):
            return True
        if not self._ssts:
            return False
        h = None
        if len(key) >= _BASE_HI:
            h = zlib.crc32(key[_BASE_HI:], zlib.crc32(key[:_BASE_LO]))
        for sst in self._ssts:
            if h is not None and not sst.bloom_may_contain_hash(table, h):
                self.bloom_point_skips += 1
                continue
            if sst.has_key(table, key):
                return True
        return False

    # -- WAL --------------------------------------------------------------

    def _wal_append(self, op: int, *parts: bytes,
                    flush: bool = True) -> None:
        if self._wal is None:
            return
        payload = b"".join(struct.pack(">I", len(p)) + p for p in parts)
        self._wal.write(_REC.pack(op, len(payload)) + payload)
        # Always push past the USERSPACE buffer before acknowledging:
        # without this, up to 8 KiB of acknowledged writes sit in the
        # Python file object and a SIGTERM/crash loses them silently —
        # found live, with every verification daemon's WAL at 0 bytes
        # after a kill. flush() is process-crash-safe (data reaches the
        # OS page cache); ``fsync`` additionally survives power loss.
        # Batch writers pass flush=False per record and call
        # _wal_flush() ONCE before the batch acknowledges (the ack
        # boundary, not the record, is the durability promise).
        _M_WAL_APPENDS.inc()
        _M_WAL_BYTES.inc(_REC.size + len(payload))
        if flush:
            if self._wal_group_ms > 0:
                self._grp_note(1)
            else:
                self._wal_flush()
                _fault("kv.wal.append", self._wal_path,
                       _REC.size + len(payload))

    def _wal_flush(self) -> None:
        self._wal.flush()
        # Between the userspace flush and the (optional) fsync: crash
        # here loses nothing on process death but everything on power
        # loss — the gap the fsync=True deployments buy away; ioerror
        # simulates the fsync itself failing (ENOSPC/EIO). The trace
        # span brackets the faultpoint too, so an armed delay here
        # stretches exactly the wal.fsync span of a traced ingest.
        with _trace.span("wal.fsync"):
            _fault("kv.wal.fsync", self._wal_path)
            if self._fsync:
                with _M_WAL_FSYNC.time():
                    os.fsync(self._wal.fileno())
        # In group mode every direct (non-deferred) flush runs under
        # the store lock — checkpoint rotation, close(), flush() — and
        # covers every record written so far: mark all issued tickets
        # durable so parked barriers wake instead of re-flushing.
        if self._wal_group_ms > 0:
            self._grp_sync_locked()

    # -- WAL group commit (Config.wal_group_ms) ---------------------------
    #
    # Appends keep writing into the WAL's userspace buffer under the
    # store lock, but the per-append flush+fsync is deferred: each
    # append takes a ticket (_grp_written), and the ACK path — after
    # releasing the store lock — parks in _wal_barrier until a group
    # flush covers its ticket. The first parked thread elects itself
    # leader, lingers up to wal_group_ms collecting followers, then
    # performs ONE flush+fsync for everything written so far. The
    # durability contract is unchanged (nothing acks before its
    # covering fsync); only the fsync count changes.

    def _grp_note(self, points: int) -> None:
        """Record a deferred-flush append (called under the store
        lock). Fires the write-side faultpoint with NO path/bytes
        context on purpose: the deferred record may still sit in the
        userspace buffer, so a torn cut here could reach into bytes an
        earlier group fsync already made durable — the site therefore
        degrades torn to a plain crash."""
        _fault("kv.wal.group.write")
        with self._grp_cond:
            self._grp_written += 1
        _M_GRP_BATCHES.inc()
        _M_GRP_POINTS.inc(points)

    def _grp_ticket(self) -> int:
        """Ticket for _wal_barrier, captured while the store lock is
        still held (every _grp_written bump happens under it). 0 =
        group mode off, nothing to wait for."""
        if self._wal_group_ms > 0 and self._wal is not None:
            return self._grp_written
        return 0

    def _grp_sync_locked(self) -> None:
        """After a direct full flush under the store lock: every
        issued ticket is covered — advance the flushed watermark and
        the durable byte position, and wake parked barriers."""
        pos = 0
        if self._wal is not None:
            try:
                pos = self._wal.tell()
            except ValueError:
                pos = 0
        with self._grp_cond:
            self._grp_flushed = self._grp_written
            self._grp_synced_pos = max(self._grp_synced_pos, pos)
            self._grp_cond.notify_all()

    def _grp_rotated_locked(self) -> None:
        """The WAL was just rotated to a fresh segment (store lock
        held): reset the durable position for the new file and bump
        the file epoch so a stale leader mid-flush on the old fd
        cannot clobber the new file's position."""
        with self._grp_cond:
            self._grp_file_epoch += 1
            self._grp_synced_pos = 0

    def _wal_group_flush(self) -> None:
        """The leader's covering flush (+fsync), run WITHOUT the store
        lock — BufferedWriter serializes internally against concurrent
        buffered appends. Raises ValueError/OSError if a rotation
        closed the file underneath us (the barrier handles it)."""
        wal = self._wal
        if wal is None:
            return
        with self._grp_cond:
            epoch = self._grp_file_epoch
            synced = self._grp_synced_pos
        # Position BEFORE the userspace flush: <= the on-disk size
        # after it, so the torn span below can never cut into bytes a
        # previous group fsync already covered (acked records all sit
        # at or below _grp_synced_pos).
        tell_pos = wal.tell()
        wal.flush()
        with _trace.span("wal.fsync"):
            _fault("kv.wal.group.fsync", self._wal_path,
                   max(tell_pos - synced, 1))
            if self._fsync:
                with _M_WAL_FSYNC.time():
                    os.fsync(wal.fileno())
        with self._grp_cond:
            if self._grp_file_epoch == epoch:
                self._grp_synced_pos = max(self._grp_synced_pos,
                                           tell_pos)
        _M_GRP_FSYNCS.inc()

    def _wal_barrier(self, ticket: int) -> None:
        """Park until a group flush covers ``ticket`` (leader-elected:
        the first uncovered caller lingers wal_group_ms to collect
        followers, then flushes for everyone). Call AFTER releasing
        the store lock — lock order is store lock -> _grp_cond."""
        if not ticket or MemKVStore._ACK_BEFORE_FSYNC:
            return
        t0 = _perf()
        cond = self._grp_cond
        linger = self._wal_group_ms / 1000.0
        while True:
            with cond:
                if self._grp_flushed >= ticket:
                    break
                if self._grp_leader:
                    # A leader is collecting or flushing; the timeout
                    # is belt-and-braces against a lost notify.
                    cond.wait(0.05)
                    continue
                self._grp_leader = True
                if linger > 0:
                    cond.wait(linger)
                target = self._grp_written
            err = None
            try:
                self._wal_group_flush()
            except BaseException as e:
                err = e
            with cond:
                self._grp_leader = False
                if err is None:
                    self._grp_flushed = max(self._grp_flushed, target)
                covered = self._grp_flushed >= ticket
                cond.notify_all()
            if err is not None:
                # A rotation/close can legitimately yank the file out
                # from under an elected leader — but only after its
                # own full flush covered every issued ticket.
                if covered and isinstance(err, (ValueError, OSError)):
                    break
                raise err
        _M_GRP_WAIT.observe((_perf() - t0) * 1000.0)

    def wal_barrier(self, ticket: int | None = None) -> None:
        """Block until every WAL record appended so far (or, with a
        ``ticket`` from a mutation's return, up to that ticket) is
        covered by a group flush. No-op outside group mode; safe to
        call without the store lock. Batch ingest calls this ONCE per
        wire batch (put_many(..., sync=False) per series, then one
        barrier) instead of once per series."""
        if self._wal_group_ms <= 0 or self._wal is None:
            return
        if ticket is None:
            with self._grp_cond:
                ticket = self._grp_written
        self._wal_barrier(ticket)

    @property
    def wal_group_ms(self) -> float:
        return self._wal_group_ms

    @wal_group_ms.setter
    def wal_group_ms(self, ms: float) -> None:
        """Set externally like sstable_codec (make_tsdb plumbs
        Config.wal_group_ms here). Enabling seeds the durable byte
        position from the current WAL end: everything already on disk
        (replayed history) must never fall inside a torn group span."""
        self._wal_group_ms = float(ms)
        if self._wal_group_ms > 0 and self._wal is not None:
            with self._grp_cond:
                try:
                    self._grp_synced_pos = max(self._grp_synced_pos,
                                               self._wal.tell())
                except ValueError:
                    pass

    def _stamp_epoch_header(self, force: bool = False) -> None:
        """Begin (or continue) this writer's ownership span in the WAL
        with an ``_OP_EPOCH`` record. ``force`` stamps unconditionally
        — a freshly rotated segment always needs a header; otherwise
        the stamp is skipped when the replayed stream already ended
        inside this writer's epoch (a clean same-epoch restart keeps
        appending without a redundant header). Opening with a replayed
        epoch ABOVE our own means this process was deposed while down:
        refuse to take the WAL at all."""
        if self._wal is None or self.writer_epoch is None:
            return
        if self._replay_epoch > self.writer_epoch:
            from opentsdb_tpu.core.errors import FencedWriterError
            raise FencedWriterError(
                f"WAL at {self._wal_path!r} already carries epoch "
                f"{self._replay_epoch}, this writer owns "
                f"{self.writer_epoch}: superseded while down",
                self.writer_epoch, self._replay_epoch)
        if force or self._replay_epoch < self.writer_epoch:
            self._wal_append(_OP_EPOCH,
                             struct.pack(">Q", self.writer_epoch))
            self._replay_epoch = self.writer_epoch

    # _REC frames the payload with a u32 length, capping one record at
    # 4 GiB. Batches whose blobs approach that are split into multiple
    # _OP_PUT_BATCH records (replay applies them in order, so the split
    # is invisible); the margin below the u32 limit leaves room for the
    # length arrays + header.
    _WAL_BATCH_LIMIT = 1 << 30

    @staticmethod
    def _batch_splits(cell_bytes: "np.ndarray") -> list[tuple[int, int]]:
        """[(start, stop)) cell ranges whose ACTUAL blob bytes each fit
        _WAL_BATCH_LIMIT (cumulative-sum greedy, so size-skewed batches
        can't overflow a chunk; a lone cell above the limit still gets
        its own record — only a single >4 GiB cell is unframeable). The
        common case (total under the limit) returns one full range."""
        n = len(cell_bytes)
        limit = MemKVStore._WAL_BATCH_LIMIT
        csum = np.cumsum(cell_bytes, dtype=np.int64)
        if n <= 1 or csum[-1] <= limit:
            return [(0, n)]
        out = []
        lo = 0
        base = 0
        while lo < n:
            # Furthest stop with csum[stop-1] - base <= limit; always
            # advance at least one cell.
            hi = int(np.searchsorted(csum, base + limit, side="right"))
            hi = max(hi, lo + 1)
            out.append((lo, hi))
            base = int(csum[hi - 1])
            lo = hi
        return out

    def _wal_append_batch(self, table: bytes, family: bytes,
                          cells: list[tuple[bytes, bytes, bytes]]) -> None:
        """One COLUMNAR WAL record for a whole put_many batch, then
        flush.

        The per-cell _OP_PUT framing (4 struct.packs + join + write per
        cell) was the single largest cost of sustained ingest at scale
        — 20.5 s of a 37 s / 4M-point profile, ~5 µs per cell — because
        a sparse-per-series workload materializes ~0.2-0.5 row-hour
        cells per point. Layout: header, three >u4 length arrays, then
        the key/qualifier/value blobs — three C-level joins and one
        write instead of any per-cell framing (the interleaved
        len-prefixed variant still cost 1.3 us/cell in the join). The
        torn-tail truncation in _replay gives a partially-written batch
        record the same crash semantics as a torn _OP_PUT."""
        if self._wal is None:
            return
        t_app0 = _perf()
        n = len(cells)
        ks, qs, vs = zip(*cells)
        kl = np.fromiter(map(len, ks), ">u4", n)
        ql = np.fromiter(map(len, qs), ">u4", n)
        vl = np.fromiter(map(len, vs), ">u4", n)
        blob = int(kl.sum()) + int(ql.sum()) + int(vl.sum())
        splits = ([(0, n)] if blob <= self._WAL_BATCH_LIMIT else
                  self._batch_splits(kl.astype(np.int64)
                                     + ql.astype(np.int64)
                                     + vl.astype(np.int64)))
        for lo, hi in splits:
            payload = b"".join((
                struct.pack(">IHH", hi - lo, len(table), len(family)),
                table, family,
                kl[lo:hi].tobytes(), ql[lo:hi].tobytes(),
                vl[lo:hi].tobytes(),
                b"".join(ks[lo:hi]), b"".join(qs[lo:hi]),
                b"".join(vs[lo:hi])))
            self._wal.write(_REC.pack(_OP_PUT_BATCH, len(payload))
                            + payload)
            _M_WAL_APPENDS.inc()
            _M_WAL_BYTES.inc(_REC.size + len(payload))
        if self._wal_group_ms > 0:
            self._grp_note(n)
            _M_WAL_APPEND.observe((_perf() - t_app0) * 1000.0)
            return
        self._wal_flush()
        _M_WAL_APPEND.observe((_perf() - t_app0) * 1000.0)
        _fault("kv.wal.append", self._wal_path,
               _REC.size + len(payload))

    def _wal_append_batch_columnar(self, table: bytes, family: bytes,
                                   key_blob: bytes, n: int, key_len: int,
                                   quals: list[bytes],
                                   vals: list[bytes]) -> None:
        """Same _OP_PUT_BATCH record as _wal_append_batch, but the key
        blob is written as-is (the caller already holds the keys as one
        contiguous buffer) — no per-key slicing or re-join."""
        if self._wal is None:
            return
        t_app0 = _perf()
        ql = np.fromiter(map(len, quals), ">u4", n)
        vl = np.fromiter(map(len, vals), ">u4", n)
        blob = n * key_len + int(ql.sum()) + int(vl.sum())
        splits = ([(0, n)] if blob <= self._WAL_BATCH_LIMIT else
                  self._batch_splits(ql.astype(np.int64)
                                     + vl.astype(np.int64) + key_len))
        for lo, hi in splits:
            payload = b"".join((
                struct.pack(">IHH", hi - lo, len(table), len(family)),
                table, family,
                np.full(hi - lo, key_len, ">u4").tobytes(),
                ql[lo:hi].tobytes(), vl[lo:hi].tobytes(),
                key_blob[lo * key_len:hi * key_len],
                b"".join(quals[lo:hi]), b"".join(vals[lo:hi])))
            self._wal.write(_REC.pack(_OP_PUT_BATCH, len(payload))
                            + payload)
            _M_WAL_APPENDS.inc()
            _M_WAL_BYTES.inc(_REC.size + len(payload))
        if self._wal_group_ms > 0:
            self._grp_note(n)
            _M_WAL_APPEND.observe((_perf() - t_app0) * 1000.0)
            return
        self._wal_flush()
        _M_WAL_APPEND.observe((_perf() - t_app0) * 1000.0)
        _fault("kv.wal.append", self._wal_path,
               _REC.size + len(payload))

    @staticmethod
    def _split_payload(payload: bytes) -> list[bytes]:
        parts = []
        off = 0
        while off < len(payload):
            (n,) = struct.unpack_from(">I", payload, off)
            off += 4
            parts.append(payload[off:off + n])
            off += n
        return parts

    def _replay(self, path: str, start: int = 0) -> int:
        """Apply every complete WAL record from byte ``start``; returns
        the valid byte count (absolute, including ``start``)."""
        with open(path, "rb") as f:
            return self._replay_file(f, start)

    def _replay_file(self, f, start: int = 0) -> int:
        """_replay over an already-open file (refresh() verifies the
        fd's inode before seeking — reopening by path would race a
        writer's WAL rotation)."""
        valid = start
        if start:
            f.seek(start)
        while True:
            hdr = f.read(_REC.size)
            if len(hdr) < _REC.size:
                break  # truncated tail: stop at last complete record
            op, plen = _REC.unpack(hdr)
            payload = f.read(plen)
            if len(payload) < plen:
                break
            if op == _OP_EPOCH:
                (e,) = struct.unpack(
                    ">Q", self._split_payload(payload)[0])
                if e < self._replay_epoch:
                    # A segment from a DEPOSED writer landed after a
                    # newer writer's records — the split-brain
                    # artifact the epoch fence exists for. Refuse
                    # everything from the stale header on: for a
                    # writer the torn-tail truncation cuts it off
                    # (those appends were never legitimately acked —
                    # their author had already been superseded); a
                    # replica simply stops its cursor here.
                    try:
                        end = os.fstat(f.fileno()).st_size
                    except OSError:
                        end = valid
                    self.fenced_bytes_refused += max(end - valid, 0)
                    break
                self._replay_epoch = e
                valid += _REC.size + plen
                continue
            valid += _REC.size + plen
            if op == _OP_PUT_BATCH:
                n, tl, fl = struct.unpack_from(">IHH", payload, 0)
                off = 8
                table = payload[off:off + tl].decode()
                off += tl
                fam = payload[off:off + fl]
                off += fl
                lo = off            # the three u32 length arrays
                kl = np.frombuffer(payload, ">u4", n, off)
                ql = np.frombuffer(payload, ">u4", n, off + 4 * n)
                vl = np.frombuffer(payload, ">u4", n, off + 8 * n)
                off += 12 * n
                # Blob starts: keys, then quals, then values.
                ko, qo = off, off + int(kl.sum())
                vo = qo + int(ql.sum())
                if _EXT is not None:
                    # Bulk replay: slice the three blobs in C and
                    # upsert the whole record in one pass. Exactly
                    # _apply_put per cell (set the cell, create the
                    # row + pending entry when absent — no tier
                    # probes, no throttle on replay), so the result
                    # is identical to the loop below; recovery of a
                    # 10M-point WAL drops from ~10 s to ~2 s.
                    mv = memoryview(payload)
                    keys = _EXT.slice_varlen(mv[ko:qo],
                                             mv[lo:lo + 4 * n])
                    quals = _EXT.slice_varlen(
                        mv[qo:vo], mv[lo + 4 * n:lo + 8 * n])
                    vals = _EXT.slice_varlen(
                        mv[vo:vo + int(vl.sum())],
                        mv[lo + 8 * n:lo + 12 * n])
                    t = self._table(table)
                    existed = _EXT.upsert_cells(t.rows, keys, fam, quals,
                                                vals, t.pending)
                    self._dirty_add_new(t, keys, existed)
                    continue
                apply_put = self._apply_put
                for lk, lq, lv in zip(kl.tolist(), ql.tolist(),
                                      vl.tolist()):
                    apply_put(table, payload[ko:ko + lk], fam,
                              payload[qo:qo + lq],
                              payload[vo:vo + lv])
                    ko += lk
                    qo += lq
                    vo += lv
                continue
            parts = self._split_payload(payload)
            table = parts[0].decode()
            if op == _OP_PUT:
                _, key, fam, qual, value = parts
                self._apply_put(table, key, fam, qual, value)
            elif op == _OP_DELETE:
                _, key, fam, *quals = parts
                self._apply_delete(table, key, fam, quals)
            elif op == _OP_DELETE_ROW:
                _, key = parts
                self._apply_delete_row(table, key)
        return valid

    def flush(self) -> None:
        """Force WAL to stable storage (reference: HBaseClient.flush)."""
        with self._lock:
            if self._wal is not None:
                self._wal.flush()
                os.fsync(self._wal.fileno())
                if self._wal_group_ms > 0:
                    self._grp_sync_locked()

    def close(self) -> None:
        with self._lock:
            try:
                if self._wal is not None:
                    try:
                        self.flush()
                    finally:
                        # A failed final fsync (ENOSPC/EIO) must still
                        # release the fds and the flock — the error
                        # propagates, but a store that stays locked
                        # wedges every later open in this process.
                        self._wal.close()
                        self._wal = None
            finally:
                for sst in self._ssts:
                    sst.close()
                self._ssts = []
                if self._lockfd is not None:
                    os.close(self._lockfd)  # releases the flock
                    self._lockfd = None

    def _simulate_crash(self) -> None:
        """TEST HOOK: release the single-writer lock WITHOUT flushing
        or closing, the way process death does (the OS drops a dead
        process's flock; unflushed state is simply lost). Crash-
        recovery tests reopen the wal path after calling this."""
        with self._lock:
            if self._lockfd is not None:
                os.close(self._lockfd)
                self._lockfd = None

    # -- cluster promotion / demotion (cluster/) --------------------------

    def _try_take_lock(self) -> bool:
        """Non-blocking attempt at the single-writer flock (the
        promoted-over-a-zombie recovery path). Returns True when
        held after the call."""
        if self._lockfd is not None:
            return True
        lockfd = os.open(self._wal_path + ".lock",
                         os.O_CREAT | os.O_RDWR, 0o644)
        try:
            fcntl.flock(lockfd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            os.close(lockfd)
            return False
        self._lockfd = lockfd
        logging.getLogger(__name__).info(
            "re-acquired single-writer lock at %s.lock",
            self._wal_path)
        return True

    def promote_writable(self, writer_epoch: int,
                         epoch_guard=None) -> None:
        """Take write ownership of this replica's store (replica
        promotion, cluster/promote.py). The caller has already bumped
        the persisted epoch (``bump_epoch``); this is the storage
        half:

        1. Try the advisory single-writer flock — but do NOT let a
           wedged-but-alive zombie (which still holds it) block the
           takeover: in cluster mode the EPOCH is the authority, the
           flock is best-effort courtesy. A deposed-but-locked zombie
           is fenced by its guard on the next mutation, and its
           appends land on an unlinked inode (step 3).
        2. Re-run the WRITER recovery path over the store (torn tails
           truncated, .old + WAL replayed — the exact crash-recovery
           code, correct in any in-flight writer state).
        3. Reopen the WAL tail under a GUARANTEED-FRESH inode (the
           PR-1 rotation discipline: pre-promotion records move to
           ``<wal>.old``, tmp + ``os.replace`` mints the new file) and
           stamp the new epoch header — the zombie's still-open fd now
           points at an unlinked inode, so even its pre-fence appends
           can never reach a file anyone replays.
        """
        with self._lock:
            if not self.read_only:
                raise ValueError("promote_writable() is for read-only "
                                 "replica stores")
            if not self._wal_path:
                raise ValueError("an in-memory store cannot be "
                                 "promoted")
            if writer_epoch < 1:
                raise ValueError(f"writer epoch must be >= 1, got "
                                 f"{writer_epoch}")
            lockfd = os.open(self._wal_path + ".lock",
                             os.O_CREAT | os.O_RDWR, 0o644)
            try:
                fcntl.flock(lockfd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                # The deposed owner is alive and still holds it. The
                # epoch fence makes proceeding safe; refusing here
                # would make a WEDGED writer (the promotion trigger!)
                # un-deposable.
                os.close(lockfd)
                lockfd = None
                logging.getLogger(__name__).warning(
                    "promoting over a held writer lock at %s.lock — "
                    "epoch fence (epoch %d) deposes the holder",
                    self._wal_path, writer_epoch)
            _fault("cluster.promote.take", self._wal_path)
            old_ssts, old_tables = self._ssts, self._tables
            old_state = self._ro_state
            self._ssts = []
            self._tables = {}
            self._ro_state = None
            self.read_only = False
            self.writer_epoch = int(writer_epoch)
            try:
                # Writer-path recovery (NOT the replica's): truncates
                # torn tails, replays .old + WAL, opens for append,
                # stamps the epoch into the current segment.
                self._open_tiers(self._wal_path)
                self._promote_rotate_locked()
            except BaseException:
                # Stay a coherent REPLICA on any failure (fault
                # injected mid-rotation, disk full): close whatever
                # half-opened, restore the pre-promotion view, release
                # the lock — the caller retries or picks another
                # target.
                for sst in self._ssts:
                    sst.close()
                if self._wal is not None:
                    self._wal.close()
                    self._wal = None
                self._ssts, self._tables = old_ssts, old_tables
                self._ro_state = old_state
                self.read_only = True
                self.writer_epoch = None
                if lockfd is not None:
                    os.close(lockfd)
                raise
            self._lockfd = lockfd
            self.epoch_guard = epoch_guard
            for sst in old_ssts:
                sst.close()
            # The generation set was replaced wholesale (a rebuild, as
            # far as cache consumers can tell): bump the rebuild
            # counter (sketch reload key) and jump the fragment-cache
            # stamp floor.
            self.rebuilds += 1
            self.mutation_seq += 1
            self._stamp_floor = self.mutation_seq
            self._base_stamps = {}
            self._stamps_snap = {}
            self._dirty_snap = {}

    def _promote_rotate_locked(self) -> None:
        """The fresh-inode WAL rotation of a promotion (checkpoint's
        rotation discipline, minus the spill): pre-promotion records
        move to ``<wal>.old`` — appended when a crash remnant already
        exists, renamed otherwise — and the fresh segment opens with
        this writer's epoch header. Recovery replays .old then the
        WAL, so a crash anywhere in here loses nothing."""
        _fault("cluster.promote.rotate", self._wal_path)
        if self._wal is not None:
            # Cover every deferred group-commit ticket before the fd
            # goes away (close() only reaches the page cache; parked
            # barriers must see their fsync happen, not vanish).
            self._wal_flush()
            self._wal.close()
            self._wal = None
        old_path = self._wal_path + ".old"
        if os.path.exists(self._wal_path):
            # COPY into .old, never rename: a rename keeps the old
            # inode LINKED (at .old — a file recovery replays), so a
            # zombie's still-open fd would keep appending into the
            # replay stream. Copying leaves the zombie's inode with no
            # name the moment the replace below lands; records it
            # appends after our read vanish with it. A crash between
            # copy and replace duplicates the WAL into .old — replay
            # is an upsert, so the double-apply is idempotent (the
            # same property checkpoint's crash-recovered .old append
            # relies on).
            with open(old_path, "ab") as dst, \
                    open(self._wal_path, "rb") as src:
                # Streamed, not one read(): a plain writer defaults to
                # manual checkpoints, so the WAL at failover time can
                # be the whole ingest history — materializing it as
                # one bytes object could OOM the promotion candidate
                # under exactly the load that killed the writer.
                import shutil as _shutil
                _shutil.copyfileobj(src, dst, 1 << 20)
                dst.flush()
                os.fsync(dst.fileno())
            # tmp-then-replace, not unlink-then-create: the tmp's
            # inode is allocated while the old WAL is still linked,
            # so the filesystem cannot recycle the number (the PR-1
            # replica-cursor lesson).
            tmp = self._wal_path + ".rotate"
            self._wal = open(tmp, "wb")
            os.replace(tmp, self._wal_path)
        else:
            self._wal = open(self._wal_path, "ab")
        self._grp_rotated_locked()
        self._stamp_epoch_header(force=True)
        self._wal_flush()

    def demote_readonly(self) -> None:
        """Deposed writer → tailing replica, in place: drop the WAL
        fd and the flock, flip read-only, and rebuild the view through
        the replica recovery path (which never truncates — the new
        writer owns the files now). The caller (TSDB.demote) holds
        the checkpoint lock so no spill is in flight."""
        with self._lock:
            if self.read_only:
                return
            if self._wal is not None:
                try:
                    self._wal.flush()
                except OSError:
                    pass  # likely an unlinked inode already; fine
                self._wal.close()
                self._wal = None
            if self._lockfd is not None:
                os.close(self._lockfd)
                self._lockfd = None
            # A frozen middle tier (fence tripped mid-checkpoint) is
            # fully covered by <wal>.old — the rotation preceded the
            # freeze — so the rebuild below reproduces it from disk.
            self._frozen = None
            self.read_only = True
            self.writer_epoch = None
            self.epoch_guard = None
            self._rebuild_locked()

    # -- checkpoint / spill ----------------------------------------------

    def checkpoint(self) -> int:
        """Spill the frozen memtable to a new sstable generation, then
        drop the pre-checkpoint WAL records. Returns rows written
        (0 = not persistent / already in progress).

        Normally an O(frozen-rows) memtable-only spill: the new
        generation is appended to the tier list and reads overlay it
        (full rewrites grew linearly with history — 28 s at 25M points,
        114 s at 75M — which dominated sustained ingest). When the
        generation count hits _MAX_GENERATIONS, a SIZE-TIERED partial
        merge collapses only the newest age-contiguous suffix of
        generations (plus frozen) whose combined size the next-older
        generation does not dwarf — so the largest, oldest generations
        are left untouched and write amplification stays logarithmic
        instead of rewriting the whole history every cap-hit (268 s of
        the 828 s 1B-run wall was the two full collapses). A FULL
        merge (every generation + frozen) runs only when the frozen
        tier holds tombstones: tombstones must mask cells in EVERY
        lower generation, and a partial merge would drop them for the
        kept prefix, resurrecting the masked cells.

        Three phases, designed so ingest/queries never wait on the merge:
          1. (brief lock) freeze the memtable as an immutable middle tier,
             rotate the WAL: pre-checkpoint records move to <wal>.old,
             writes continue into a fresh WAL.
          2. (no lock) stream the spill into a temp file, fsync,
             atomically rename to the new generation.
          3. (brief lock) open the new generation, write the manifest
             (the authoritative generation set — stray files from a
             crash between manifest write and unlinks are deleted at
             next load), discard the frozen tier, unlink <wal>.old.
        Crash-safe: <wal>.old survives until the new generation is durable
        (sstable.write_sstable fsyncs the file AND its directory before
        phase 3); recovery replays <wal>.old then the WAL, which is
        idempotent over any manifest state.
        """
        if self._sst_path is None or self.read_only:
            return 0
        if self.epoch_guard is not None:
            # Fence BEFORE the rotation: a deposed writer's checkpoint
            # renames WAL files BY PATH and rewrites the manifest —
            # the single most destructive thing a zombie can do to the
            # store its successor now owns. force=True: a checkpoint
            # is rare enough to afford a fresh read of the epoch file.
            self.epoch_guard.check(force=True)
        if self._lockfd is None and self.writer_epoch is not None:
            # A promotion over a still-held zombie flock came out
            # lockless (epoch fence was the authority). Re-acquire
            # opportunistically once the zombie exits, so a later
            # NON-cluster writer — to which no epoch fence applies —
            # is refused by the lock like on any other store.
            self._try_take_lock()
        old_path = self._wal_path + ".old"
        with _trace.timed("checkpoint.phase", phase="freeze"), self._lock:
            if self._frozen is not None:
                return 0  # merge already in flight
            self._frozen = self._tables
            self._tables = {name: _Table() for name in self._frozen}
            self.mutation_seq += 1
            if self._wal is not None:
                # Cover every deferred group-commit ticket before the
                # fd goes away — parked barriers wake durable, and a
                # leader racing the close sees its ticket covered.
                if self._wal_group_ms > 0:
                    self._wal_flush()
                self._wal.close()
                if os.path.exists(old_path):
                    # A crash-recovered .old is still live state: append the
                    # current WAL to it rather than clobbering it.
                    with open(old_path, "ab") as dst, \
                            open(self._wal_path, "rb") as src:
                        dst.write(src.read())
                        dst.flush()
                        os.fsync(dst.fileno())
                    # Recreate the WAL under a GUARANTEED-FRESH inode
                    # (empty tmp + os.replace) rather than truncating
                    # in place: replicas key their suffix-replay
                    # position on the WAL's inode, and an in-place 'wb'
                    # kept the inode while resetting the offset — once
                    # the regrown WAL crossed a replica's stale offset,
                    # its replay seeked mid-record and could misparse
                    # arbitrary bytes as records (frames carry no
                    # checksum). tmp-then-replace, not unlink-then-
                    # create: the tmp's inode is allocated while the
                    # old WAL is still linked, so the filesystem cannot
                    # hand the replacement the just-freed inode number
                    # (tmpfs recycles eagerly). A crash in between
                    # surfaces either WAL state; recovery replays
                    # <wal>.old (which holds every record) first.
                    tmp = self._wal_path + ".rotate"
                    self._wal = open(tmp, "wb")
                    os.replace(tmp, self._wal_path)
                else:
                    os.replace(self._wal_path, old_path)
                    self._wal = open(self._wal_path, "ab")
                self._grp_rotated_locked()
                # A cluster-mode writer begins the fresh segment with
                # its epoch header (replay-side fence anchor).
                self._stamp_epoch_header(force=True)
            frozen = self._frozen
            spill_keys = None
            if self.record_spill_keys:
                # Keys leaving the memtable this checkpoint (row
                # tombstones included: a delete of spilled data must
                # reach the rollup fold too, or stale summaries would
                # keep serving the deleted points).
                spill_keys = {
                    name: list(ft.rows) + list(ft.row_tombs)
                    for name, ft in frozen.items()
                    if ft.rows or ft.row_tombs}
            gens = list(self._ssts)
            tombstoned = any(ft.row_tombs or ft.tombs
                             for ft in frozen.values())
            if tombstoned:
                keep: list[SSTable] = []
                merge_gens = gens
            elif len(gens) + 1 >= self._MAX_GENERATIONS:
                keep, merge_gens = self._select_merge_suffix(gens)
            else:
                keep, merge_gens = gens, []
            use_merge = tombstoned or bool(merge_gens)
            empty = not any(ft.rows or ft.row_tombs
                            for ft in frozen.values())
            out_path = self._next_generation_path()

        if empty:
            # Nothing to spill, but the WAL rotation above must still
            # conclude: a WAL whose records net out to an empty
            # memtable (put-then-delete churn on unspilled rows) holds
            # no state the generations don't — dropping <wal>.old loses
            # nothing, and skipping here would let idle/churn daemons'
            # timer checkpoints grow the WAL without bound while an
            # empty generation file accreted per call.
            with self._lock:
                self._fold_touch_locked(self._frozen)
                self._frozen = None
                self.mutation_seq += 1
                if os.path.exists(old_path):
                    os.unlink(old_path)
            return 0

        if use_merge:
            # Copy-merge collapse (sstable.merge_sstables): unique-key
            # records relocate verbatim at IO speed; only multi-source
            # keys and the frozen tier re-frame (tombstones applied
            # there). The streamed per-row merge this replaces cost
            # 20.7 us/row — 145 s at the 7M-row mark of the 1B run.
            frozen_payload = {
                name: (ft.rows, ft.row_tombs, bool(ft.tombs))
                for name, ft in frozen.items()}
        else:
            def spill_tables():
                # Memtable-only: by the tombstone test above the frozen
                # tier holds no tombstones, so every cell value is
                # real bytes and no lower-generation read is needed.
                # Sorted keys + the row dict itself: write_sstable_bulk
                # frames records straight off the memtable in C — the
                # per-row Python framing/materialization was ~5 us/row,
                # most of a 22 s spill at 4.4M rows.
                return {name: ([k for k in sorted(ft.rows) if ft.rows[k]],
                               ft.rows)
                        for name, ft in frozen.items()}

        try:
            # End of phase 1: the WAL is rotated (<wal>.old holds every
            # pre-checkpoint record), the memtable is frozen, nothing
            # spilled yet. Crash here must recover purely from
            # .old + WAL replay; raise exercises the thaw path below.
            _fault("kv.checkpoint.freeze", self._wal_path)
            # kwarg only when compressing: the default spill call shape
            # stays identical (tests stub these writers by signature).
            kw = {"codec": self.sstable_codec} \
                if self.sstable_codec not in (None, "none") else {}
            with _trace.timed("checkpoint.phase", phase="spill"):
                n = (merge_sstables(out_path, merge_gens, frozen_payload,
                                    **kw)
                     if use_merge
                     else write_sstable_bulk(out_path, spill_tables(),
                                             **kw))
        except Exception:
            # Disk full or similar mid-merge: thaw the frozen tier back
            # under the live memtable so the store isn't wedged (a stuck
            # _frozen would make every future checkpoint a no-op and let
            # the WAL grow without bound). <wal>.old stays on disk; the
            # next checkpoint appends the live WAL to it, and recovery
            # replays .old + WAL, so durability is unaffected.
            with self._lock:
                self._thaw_frozen_locked()
            raise

        with _trace.timed("checkpoint.phase", phase="commit"), self._lock:
            # Phase 3 failures (sstable open, manifest tmp write right
            # after a near-full-disk spill) get the SAME recovery as a
            # spill failure: drop the new generation and thaw — a stuck
            # _frozen would no-op every later checkpoint and grow the
            # WAL without bound, with durability intact but the daemon
            # degraded until restart.
            new_sst = None
            unlink_new = True
            try:
                if self.epoch_guard is not None:
                    # Re-fence at the COMMIT: a promotion that landed
                    # while phase 2 streamed must stop this checkpoint
                    # before it rewrites the manifest and unlinks
                    # <wal>.old out from under the new owner. The
                    # exception path below already knows how to back a
                    # failed commit out (unlink the new generation,
                    # thaw the frozen tier).
                    self.epoch_guard.check(force=True)
                new_sst = SSTable(out_path)
                # The new generation is durable but the manifest does
                # not name it yet: crash leaves it a stray the next
                # load deletes (.old still replays everything); raise
                # exercises the unlink-and-thaw recovery below.
                _fault("kv.checkpoint.commit", out_path)
                # The new generation replaces exactly the merged
                # age-contiguous suffix (all of them on a full merge,
                # none on a plain spill), preserving overlay order:
                # everything in `keep` is strictly older than what the
                # new generation holds.
                dropped = merge_gens
                self._ssts = keep + [new_sst]
                # Manifest BEFORE unlinking: a crash in between leaves
                # stray files the next load deletes (they are never
                # opened, so dropped cells cannot resurrect).
                try:
                    self._write_manifest([s.path for s in self._ssts])
                except Exception:
                    old = keep + merge_gens
                    self._ssts = old
                    # The failure point is ambiguous: the new manifest
                    # may already be DURABLE (os.replace landed, the
                    # directory fsync failed). Unlinking the new
                    # generation under a durable manifest that names it
                    # would make every OLD generation a manifest-stray
                    # — deleted at next open, silently losing all
                    # previously spilled rows. Restore the old
                    # manifest first; if even that fails, keep the new
                    # file: both (old manifest, stray new file) and
                    # (new manifest, new file) are consistent states.
                    try:
                        self._write_manifest([s.path for s in old])
                    except Exception:
                        unlink_new = False
                    raise
            except Exception:
                if new_sst is not None:
                    new_sst.close()
                if unlink_new:
                    try:
                        os.unlink(out_path)
                    except OSError:
                        pass
                self._thaw_frozen_locked()
                raise
            self._frozen = None
            self.mutation_seq += 1
            # Manifest durable, dropped generations + <wal>.old not yet
            # unlinked: crash leaves strays (deleted at next load) and
            # an idempotently-replayable .old. Safe for raise too — the
            # commit is complete; only cleanup remains.
            _fault("kv.checkpoint.manifest", self._wal_path)
            # The frozen tier retires: fold its transition stamps into
            # the store-level map so fragments built while (or before)
            # its rows were live keep invalidating — including bases a
            # create-then-delete netted back to clean, which no longer
            # appear in any dirty set but may sit inside a cached
            # fragment.
            self._fold_touch_locked(frozen)
            if spill_keys is not None:
                for name, ks in spill_keys.items():
                    self._last_spill_keys.setdefault(name, []).extend(ks)
                # The frozen tier's dirty index IS the spilled keys'
                # base refcounts (rows + row tombstones): carry it as
                # the undrained-spill dirty set, summed like the key
                # record itself.
                for name, ft in frozen.items():
                    if ft.dirty:
                        sd = self._spill_dirty.setdefault(name, {})
                        for b, c in ft.dirty.items():
                            sd[b] = sd.get(b, 0) + c
            for g in dropped:
                path = g.path
                g.close()
                try:
                    os.unlink(path)
                except OSError:
                    pass
            if os.path.exists(old_path):
                os.unlink(old_path)
        return n

    @staticmethod
    def _select_merge_suffix(gens: "list[SSTable]",
                             ) -> "tuple[list[SSTable], list[SSTable]]":
        """Size-tiered pick at the generation cap: absorb older
        generations into the merge only while each is no larger than
        everything newer already being merged. This yields geometric
        tiers — the oldest, largest generations are kept verbatim and
        the generation count stays bounded (the suffix always absorbs
        at least one existing generation, so each partial merge
        shrinks the count by at least one... or holds it at cap-1 in
        the steady state). Returns (keep-prefix, merge-suffix), both
        age-ordered.

        The frozen tier's sstable footprint is estimated as the size
        of the NEWEST generation (steady-state spill windows are
        equal). Using the rotated <wal>.old size instead degenerated
        in the 1B run: WAL bytes run ~2.3x the sstable bytes for the
        same data, the over-estimate dragged the accumulated big
        generation into EVERY cap-hit, and the per-checkpoint merge
        grew linearly (5.2M -> 11.4M rows over 10 checkpoints —
        quadratic total IO, the exact pathology tiering exists to
        avoid)."""
        def size(g):
            try:
                return os.path.getsize(g.path)
            except OSError:
                # Unreadable: treat as too big to absorb — the loop
                # stops at it. As the SEED that would invert into
                # absorb-everything, so the seed uses 0 instead (the
                # pick then merges just the newest gen + frozen, the
                # minimal safe choice).
                return None
        i = len(gens) - 1          # always absorb the newest
        newest = size(gens[-1])
        acc = 2 * (newest or 0)    # + the frozen tier, estimated equal
        while i > 0:
            s = size(gens[i - 1])
            if s is None or s > acc:
                break
            acc += s
            i -= 1
        return gens[:i], gens[i:]

    def _fold_touch_locked(self, tables: "dict[str, _Table]") -> None:
        """Fold retiring tiers' transition stamps into the store-level
        map (max wins). Caller holds the lock."""
        for name, ft in tables.items():
            if not ft.touch:
                continue
            st = self._base_stamps.setdefault(name, {})
            for b, v in ft.touch.items():
                if st.get(b, -1) < v:
                    st[b] = v

    def _thaw_frozen_locked(self) -> None:
        """Fold the frozen middle tier back under the live memtable
        after a failed checkpoint (caller holds the lock). Live cells
        win; row tombstones written while the merge was in flight keep
        masking the thawed rows."""
        for name, ft in self._frozen.items():
            live = self._tables[name]
            for k, row in ft.rows.items():
                if k in live.row_tombs:
                    continue  # deleted while merge was in flight
                merged = dict(row)
                merged.update(live.rows.get(k, {}))
                live.rows[k] = merged
            live.row_tombs |= ft.row_tombs
            # Tombstone cells travel back with the rows: the counter
            # must too, or the RETRY checkpoint would pick the fast
            # tombstone-free spill and feed None values to
            # write_sstable (and, had that written, resurrect the
            # masked lower-generation cells).
            live.tombs += ft.tombs
            for k in ft.rows:
                live.note_insert(k)
            live.rebuild_dirty(self.mutation_seq + 1)
        self._fold_touch_locked(self._frozen)
        self._frozen = None
        self.mutation_seq += 1

    # -- mutation ---------------------------------------------------------

    def _apply_put(self, table: str, key: bytes, family: bytes,
                   qualifier: bytes, value: bytes) -> None:
        t = self._table(table)
        row = t.rows.get(key)
        if row is None:
            row = t.rows[key] = {}
            t.note_insert(key)
            t.dirty_add(key, self.mutation_seq)
        row[(family, qualifier)] = value

    def _apply_delete(self, table: str, key: bytes, family: bytes,
                      qualifiers: list[bytes]) -> None:
        t = self._table(table)
        spilled = (key not in t.row_tombs
                   and self._lower_tier_has(t, table, key))
        row = t.rows.get(key)
        if row is None:
            if not spilled:
                return
            row = t.rows[key] = {}
            t.note_insert(key)
            t.dirty_add(key, self.mutation_seq)
        for q in qualifiers:
            if spilled:
                row[(family, q)] = None  # tombstone masks the sstable cell
                t.tombs += 1
            else:
                row.pop((family, q), None)
        if not row:
            del t.rows[key]
            t.note_delete()
            t.dirty_sub(key, self.mutation_seq)

    def _apply_delete_row(self, table: str, key: bytes) -> None:
        t = self._table(table)
        if t.rows.pop(key, None) is not None:
            t.note_delete()
            t.dirty_sub(key, self.mutation_seq)
        if self._lower_tier_has(t, table, key) \
                and key not in t.row_tombs:
            t.row_tombs.add(key)
            t.dirty_add(key, self.mutation_seq)

    def _check_throttle(self, table: str, key: bytes) -> None:
        # Only throttle puts that would create a NEW row: updates to
        # existing rows (including compaction rewrites, which relieve
        # pressure) must keep flowing or backpressure can never clear.
        if self.throttle_rows is not None and \
                len(self._table(table).rows) >= self.throttle_rows and \
                key not in self._table(table).rows:
            raise PleaseThrottleError(
                f"table '{table}' holds >= {self.throttle_rows} rows")

    def put(self, table: str, key: bytes, family: bytes, qualifier: bytes,
            value: bytes, durable: bool = True) -> None:
        self._check_writable()
        with self._lock:
            self._check_throttle(table, key)
            self.mutation_seq += 1
            if durable:
                self._wal_append(_OP_PUT, table.encode(), key, family,
                                 qualifier, value)
            self._apply_put(table, key, family, qualifier, value)
            ticket = self._grp_ticket()
        self._wal_barrier(ticket)

    def put_many(self, table: str, family: bytes,
                 cells: list[tuple[bytes, bytes, bytes]],
                 durable: bool = True, sync: bool = True) -> list[bool]:
        """Batched put: one lock acquisition and one existence probe per
        distinct key for the whole batch — the ingest hot path writes one
        cell per row-hour, so per-call locking dominated before this.
        Semantics identical to a put() loop (WAL order, throttle check
        per new row, partial application if throttled mid-batch).

        ``sync=False`` (group-commit mode only) returns WITHOUT waiting
        for the covering group fsync: the caller batches several
        put_many calls and then issues ONE ``wal_barrier()`` before
        acknowledging any of them (server/wire.ingest_batch).
        """
        self._check_writable()
        existed: list[bool] = []
        if not cells:
            return existed
        tenc = table.encode()
        ticket = 0
        try:
            with self._lock:
                self.mutation_seq += 1
                t = self._table(table)
                rows = t.rows
                # With no lower tiers the memtable is the whole truth, so
                # existence is one dict probe (the default-config hot
                # path).
                pure_mem = not self._ssts and self._frozen is None
                throttle = self.throttle_rows
                wal = self._wal is not None and durable
                keys = [c[0] for c in cells]
                quals = [c[1] for c in cells]
                vals = [c[2] for c in cells]
                fast = self._try_fast_batch(
                    table, t, family, keys, quals, vals,
                    (lambda: self._wal_append_batch(tenc, family, cells))
                    if wal else None)
                if fast is not None:
                    existed = fast
                else:
                    batch_ok = False
                    try:
                        for key, qualifier, value in cells:
                            row = rows.get(key)
                            if row is None:
                                if throttle is not None \
                                        and len(rows) >= throttle:
                                    err = PleaseThrottleError(
                                        f"table '{table}' holds >= "
                                        f"{throttle} rows")
                                    err.partial_existed = existed
                                    raise err
                                e = (False if pure_mem
                                     else self._has_row_locked(table,
                                                               key))
                            else:
                                e = True if pure_mem \
                                    else self._has_row_locked(table, key)
                            if row is None:
                                row = rows[key] = {}
                                t.note_insert(key)
                                t.dirty_add(key, self.mutation_seq)
                            row[(family, qualifier)] = value
                            existed.append(e)
                        batch_ok = True
                    finally:
                        if wal and existed:
                            # ONE batch WAL record + flush covering
                            # exactly the applied prefix (len(existed)
                            # cells), written in a finally because a
                            # mid-batch throttle has already APPLIED
                            # (and will acknowledge, via
                            # partial_existed) the earlier cells: their
                            # records must reach the OS before the
                            # exception escapes, same promise as the
                            # success path. Writing AFTER the mutations
                            # is equivalent to put()'s
                            # WAL-before-mutation order here: the lock
                            # is held for the whole batch, so no reader
                            # observes mid-batch state, and an
                            # in-process crash loses the unacknowledged
                            # memtable state along with the unwritten
                            # record. The ack boundary, not the record,
                            # is the durability unit. A WAL failure
                            # (e.g. ENOSPC) must not REPLACE an
                            # in-flight exception, though: callers rely
                            # on PleaseThrottleError.partial_existed to
                            # know which cells applied, so the WAL
                            # error surfaces only when the batch itself
                            # succeeded. (A local flag, not
                            # sys.exc_info(): exc_info also sees a
                            # HANDLED exception in any CALLER's except
                            # block, which would silently swallow real
                            # flush failures for callers running retry
                            # loops.)
                            try:
                                self._wal_append_batch(
                                    tenc, family, cells[:len(existed)])
                            except Exception:
                                if batch_ok:
                                    raise
                                # Can't replace the in-flight
                                # exception, but a swallowed WAL
                                # failure means the applied cells'
                                # durability promise is BROKEN until
                                # the next successful flush — leave a
                                # trace.
                                self.wal_swallowed_flush_errors += 1
                                logging.getLogger(__name__).exception(
                                    "WAL batch append failed during "
                                    "exceptional put_many exit; %d "
                                    "applied cells not yet durable",
                                    len(existed))
                ticket = self._grp_ticket()
        except BaseException:
            # An exceptional exit (mid-batch throttle) has already
            # applied — and will acknowledge, via partial_existed — a
            # prefix of the batch: in group mode those records are
            # still unflushed tickets, so attempt the covering barrier
            # before the exception escapes. A barrier failure must not
            # replace the in-flight error (same contract as the WAL
            # append above).
            if sync and self._wal_group_ms > 0:
                try:
                    self.wal_barrier()
                except Exception:
                    self.wal_swallowed_flush_errors += 1
                    logging.getLogger(__name__).exception(
                        "group-commit barrier failed during "
                        "exceptional put_many exit")
            raise
        if sync:
            self._wal_barrier(ticket)
        return existed

    def _dirty_add_new(self, t: _Table, keys: list[bytes],
                       existed: list[bool]) -> None:
        """Index the bases of the rows a bulk upsert CREATED (existed
        False — the C pass reports intra-batch duplicates as existing,
        so each new row counts exactly once)."""
        add = t.dirty_add
        seq = self.mutation_seq
        for k, e in zip(keys, existed):
            if not e:
                add(k, seq)

    def _try_fast_batch(self, table: str, t: _Table, family: bytes,
                        keys: list[bytes], quals: list[bytes],
                        vals: list[bytes], wal_cb) -> "list[bool] | None":
        """The bulk batch-put path shared by put_many and
        put_many_columnar (one copy, so the subtle semantics — throttle
        bound, dup-aware existed flags, pending-index update, WAL
        inside the lock — cannot drift). Caller holds _lock and has
        validated lengths. Returns existed, or None when the batch is
        irregular (possible mid-batch throttle trip, or duplicate keys
        without the C upsert) and must take the per-cell loop.

        Bulk set/dict operations replace that loop, whose per-cell
        function-call overhead (note_insert, dict.get, per-cell WAL
        framing) was ~3.7 us/cell — the dominant cost of at-scale
        ingest. ``wal_cb`` writes the batch's WAL record (None when
        durability is off)."""
        rows = t.rows
        n = len(keys)
        pure_mem = not self._ssts and self._frozen is None
        throttle = self.throttle_rows
        # Conservative bound (assumes every key new): when it holds, a
        # mid-batch throttle trip is impossible.
        throttle_ok = throttle is None or len(rows) + n <= throttle
        if _EXT is not None and pure_mem and throttle_ok:
            # One C pass does the whole upsert + existed flags + the
            # pending-index adds, in lockstep with each row insert
            # (full put_many semantics incl. intra-batch duplicate
            # keys; sound only pure-memtable, where existence ==
            # presence in rows and tombstones can't exist). The
            # throttle bound is conservative (assumes every key new),
            # so a trip is impossible inside the pass.
            existed = _EXT.upsert_cells(
                rows, keys, family, quals, vals, t.pending)
            self._dirty_add_new(t, keys, existed)
            if wal_cb is not None:
                wal_cb()
            return existed
        ks = set(keys)
        # Lower-tier candidate prefilter: a key can only exist below
        # the live memtable if it is in the frozen memtable or inside
        # the sstable's key range. Sound as a filter because the exact
        # probe (_has_row_locked) remains the oracle for every
        # surviving candidate — it only drops keys NO lower tier can
        # hold. Time-ordered ingest (new base-times sort after every
        # spilled key) passes almost nothing through, which keeps
        # post-checkpoint sustained ingest off the 1 us/key bisect.
        lower = set()
        if not pure_mem:
            if self._frozen is not None:
                ft = self._frozen.get(table)
                if ft is not None:
                    lower |= ft.rows.keys() & ks
            for sst in self._ssts:
                bounds = sst.key_bounds(table)
                if bounds is not None:
                    lo, hi = bounds
                    lower |= {k for k in ks if lo <= k <= hi}
        if _EXT is not None and throttle_ok and not lower:
            # No batch key can touch a lower tier, so memtable presence
            # is existence and the C upsert stays sound post-checkpoint
            # (the sustained-ingest steady state). One nuance: a live
            # all-tombstone row reads as existed=True where the exact
            # probe could say False — benign, existed only enqueues a
            # compaction that then no-ops.
            existed = _EXT.upsert_cells(
                rows, keys, family, quals, vals, t.pending)
            self._dirty_add_new(t, keys, existed)
            if wal_cb is not None:
                wal_cb()
            return existed
        if len(ks) != n:
            return None
        dups = rows.keys() & ks
        if throttle is not None and \
                len(rows) + n - len(dups) > throttle:
            return None
        if pure_mem:
            existed = ([False] * n if not dups
                       else [k in dups for k in keys])
        else:
            candidates = dups | lower
            if candidates:
                hrl = self._has_row_locked
                present = {k for k in candidates if hrl(table, k)}
                existed = [k in present for k in keys]
            else:
                existed = [False] * n
        if not dups:
            if _EXT is not None:
                _EXT.rows_update_new(rows, keys, family, quals, vals)
            else:
                rows.update((k, {(family, q): v})
                            for k, q, v in zip(keys, quals, vals))
            t.pending.update(ks)
            for k in ks:
                t.dirty_add(k, self.mutation_seq)
        else:
            for k, q, v in zip(keys, quals, vals):
                row = rows.get(k)
                if row is None:
                    rows[k] = {(family, q): v}
                    t.dirty_add(k, self.mutation_seq)
                else:
                    row[(family, q)] = v
            t.pending.update(ks - dups)
        if wal_cb is not None:
            wal_cb()
        return existed

    def put_many_columnar(self, table: str, family: bytes,
                          key_blob: bytes, key_len: int,
                          quals: list[bytes], vals: list[bytes],
                          durable: bool = True,
                          sync: bool = True) -> list[bool]:
        """Columnar batched put: keys arrive as one contiguous blob that
        flows straight through to the WAL record. Shares the bulk fast
        path with put_many; anything irregular zips the triples and
        delegates to put_many (identical semantics). ``sync=False``:
        see put_many."""
        self._check_writable()
        n = len(quals)
        L = key_len
        if len(vals) != n or len(key_blob) != n * L:
            # Mis-framed inputs must fail loudly HERE: the WAL record
            # trusts n * key_len, so a silent mismatch would corrupt
            # durable state on replay.
            raise ValueError(
                f"columnar batch mismatch: {len(key_blob)} key bytes, "
                f"key_len {L}, {n} quals, {len(vals)} vals")
        if n == 0:
            return []
        if _EXT is not None:
            keys = _EXT.slice_keys(key_blob, L)
        else:
            keys = [key_blob[i:i + L] for i in range(0, n * L, L)]
        with self._lock:
            self.mutation_seq += 1
            t = self._table(table)
            wal = self._wal is not None and durable
            fast = self._try_fast_batch(
                table, t, family, keys, quals, vals,
                (lambda: self._wal_append_batch_columnar(
                    table.encode(), family, key_blob, n, L, quals,
                    vals)) if wal else None)
            ticket = self._grp_ticket()
        if fast is not None:
            if sync:
                self._wal_barrier(ticket)
            return fast
        return self.put_many(table, family, list(zip(keys, quals, vals)),
                             durable=durable, sync=sync)

    def delete(self, table: str, key: bytes, family: bytes,
               qualifiers: list[bytes]) -> None:
        self._check_writable()
        hook = self.delete_hook
        if hook is not None:
            hook(table, key)
        with self._lock:
            self.mutation_seq += 1
            self._wal_append(_OP_DELETE, table.encode(), key, family,
                             *qualifiers)
            self._apply_delete(table, key, family, qualifiers)
            ticket = self._grp_ticket()
        self._wal_barrier(ticket)

    def delete_row(self, table: str, key: bytes) -> None:
        self._check_writable()
        hook = self.delete_hook
        if hook is not None:
            hook(table, key)
        with self._lock:
            self.mutation_seq += 1
            self._wal_append(_OP_DELETE_ROW, table.encode(), key)
            self._apply_delete_row(table, key)
            ticket = self._grp_ticket()
        self._wal_barrier(ticket)

    # -- reads ------------------------------------------------------------

    def get(self, table: str, key: bytes,
            family: bytes | None = None) -> list[Cell]:
        with self._lock:
            row = self._merged_row(table, key)
            if not row:
                return []
            cells = [Cell(key, f, q, v) for (f, q), v in row.items()
                     if family is None or f == family]
            cells.sort(key=lambda c: (c.family, c.qualifier))
            return cells

    def _snapshot_keys(self, table: str, start: bytes,
                       stop: bytes,
                       skip_paths: "set[str] | None" = None,
                       ) -> list[bytes]:
        """Key snapshot across all tiers (live memtable + frozen +
        sstable, tombstone-excluded). Caller holds the lock. One
        definition for scan() and scan_raw() so tier-merge fixes can't
        diverge the two. ``skip_paths``: generations the caller's
        series-bloom prefilter proved irrelevant."""
        t = self._table(table)
        keys = t.range_keys(start, stop)
        ft = self._frozen.get(table) if self._frozen else None
        extra = set()
        if ft is not None:
            extra.update(k for k in ft.range_keys(start, stop)
                         if k not in t.rows and k not in t.row_tombs)
        for sst in self._ssts:
            if skip_paths and sst.path in skip_paths:
                continue
            extra.update(
                k for k in sst.scan_keys(table, start, stop)
                if k not in t.rows and k not in t.row_tombs
                and not (ft is not None and (k in ft.rows
                                             or k in ft.row_tombs)))
        if extra:
            keys = sorted(set(keys) | extra)
        return keys

    def scan(self, table: str, start: bytes, stop: bytes,
             family: bytes | None = None,
             key_regexp: bytes | None = None) -> Iterator[list[Cell]]:
        """Yield one sorted cell-list per row with key in [start, stop).

        ``key_regexp`` applies a DOTALL bytes regex to the whole key —
        parity with the HBase KeyRegexpFilter used for tag filtering
        (reference TsdbQuery.createAndSetFilter :433-492).

        Snapshot semantics: keys are snapshotted at call time; rows deleted
        mid-scan are skipped, rows mutated mid-scan show their new cells —
        the same weak guarantees an HBase scanner gives across RPC batches.
        """
        pattern = re.compile(key_regexp, re.S) if key_regexp else None
        with self._lock:
            keys = self._snapshot_keys(table, start, stop)
        for key in keys:
            if pattern is not None and not pattern.match(key):
                continue
            with self._lock:
                row = self._merged_row(table, key)
                if not row:
                    continue
                cells = [Cell(key, f, q, v) for (f, q), v in row.items()
                         if family is None or f == family]
            cells.sort(key=lambda c: (c.family, c.qualifier))
            if cells:
                yield cells

    def _seek_keys(self, table: str, start: bytes, stop: bytes,
                   series_keys: "list[bytes]",
                   skip_paths: "set[str] | None",
                   ) -> list[bytes] | None:
        """The row keys a selective scan of [start, stop) can find, in
        key order, formed without listing the range: each of
        ``series_keys`` under each base hour of the range (a row key is
        metric + u32(base hour) + tags, core/codec.row_key). None where
        the walk is the cheaper way or the bounds are not a metric's
        base hours. Caller holds the lock.

        Which way is decided by what the tiers say of themselves: two
        bisects a tier count the keys the walk would list, and a
        candidate costs a bisect in every tier it is probed in."""
        if not (len(start) == len(stop) == _BASE_HI
                and start[:_BASE_LO] == stop[:_BASE_LO]):
            return None
        metric = start[:_BASE_LO]
        lo = int.from_bytes(start[_BASE_LO:], "big")
        hi = int.from_bytes(stop[_BASE_LO:], "big")
        bases = range(lo + -lo % MAX_TIMESPAN, hi, MAX_TIMESPAN)
        listed = self._table(table).range_count(start, stop)
        tiers = 1
        ft = self._frozen.get(table) if self._frozen else None
        if ft is not None:
            listed += ft.range_count(start, stop)
            tiers += 1
        for sst in self._ssts:
            if not (skip_paths and sst.path in skip_paths):
                listed += sst.range_count(table, start, stop)
                tiers += 1
        if len(bases) * len(series_keys) * tiers * _SEEK_MARGIN > listed:
            return None
        tails = sorted({k[_BASE_LO:] for k in series_keys
                        if k[:_BASE_LO] == metric})
        heads = [metric + b.to_bytes(TIMESTAMP_BYTES, "big")
                 for b in bases]
        return [h + t for h in heads for t in tails]

    def scan_raw(self, table: str, start: bytes, stop: bytes,
                 family: bytes | None = None,
                 key_regexp: bytes | None = None, chunk: int = 1024,
                 series_hint: "np.ndarray | None" = None,
                 series_keys: "list[bytes] | None" = None,
                 ) -> Iterator[tuple[bytes, list[tuple[bytes, bytes]]]]:
        """Batched form of scan() for the columnar decode path: rows as
        (key, sorted [(qualifier, value), ...]), the lock taken once per
        ``chunk`` keys and no Cell allocations. Same snapshot semantics
        as scan(); a 1M-point query scans ~100k+ row-hours, so the
        per-row lock/namedtuple/generator overhead of the cell API was
        the single largest host cost of the cold query path (profiled:
        ~16 us/row, more than the vectorized decode itself).

        ``series_hint`` and ``series_keys`` (see KVStore.scan_raw) both
        rest on the caller's list being a superset of the series with
        stored rows that ``key_regexp`` matches, which the writer's
        series directory gives and a replica's does not.

        The hint prunes generations whose series bloom excludes every
        candidate — on a high-file-count store most generations hold
        disjoint time ranges OF THE SAME series, but tag-filtered
        dashboards and sparse metrics leave whole generations with
        nothing to say. Skips are decided ONCE per scan against the
        then-current generation set and matched by path thereafter: a
        generation swapped in mid-scan is simply not skipped
        (conservative), and one dropped mid-scan vanishes from
        self._ssts like any other scan.

        The keys select the rows: where they are few beside the keys
        of the range (_seek_keys), the scan probes each series under
        each base hour through the tier merge and neither lists the
        range nor applies the regexp; a candidate no tier holds reads
        as a deleted row does. Otherwise, and always without them, it
        walks the range and filters. Either way the same rows come
        back in the same order."""
        with self._lock:
            skip_paths: set[str] | None = None
            if series_hint is not None and len(series_hint) \
                    and self._ssts:
                skip_paths = set()
                for sst in self._ssts:
                    if not sst.bloom_may_contain(table, series_hint):
                        skip_paths.add(sst.path)
                        self.bloom_files_skipped += 1
                if not skip_paths:
                    skip_paths = None
            keys = None
            if key_regexp and series_keys is not None:
                keys = self._seek_keys(table, start, stop, series_keys,
                                       skip_paths)
            seek = keys is not None
            if not seek:
                keys = self._snapshot_keys(table, start, stop,
                                           skip_paths)
        if key_regexp:
            (_M_SCAN_SEEK if seek else _M_SCAN_WALK).inc()
            sp = _trace.current_span()
            if sp is not None:
                # Summed, because a sharded store's fan-out lands on
                # one span; 0/1 and one scan's candidates otherwise.
                t = sp.tags
                t["seek"] = t.get("seek", 0) + seek
                t["probes"] = t.get("probes", 0) + (len(keys) if seek
                                                    else 0)
            if not seek:
                pattern = re.compile(key_regexp, re.S)
                keys = [k for k in keys if pattern.match(k)]
        for i in range(0, len(keys), chunk):
            out = []
            with self._lock:
                # Tier state re-checked UNDER THE LOCK each chunk: a
                # concurrent checkpoint() can freeze the live memtable
                # between chunks, and a stale fast-path would then read
                # the freshly-emptied live dict and silently drop rows.
                if not self._ssts and self._frozen is None:
                    # No lower tiers => no tombstones; read the live
                    # memtable dict directly (skips a function call +
                    # tier checks per row — this loop runs per row-hour
                    # over the whole scanned range).
                    rows_get = self._table(table).rows.get
                    for key in keys[i:i + chunk]:
                        row = rows_get(key)
                        if not row:
                            continue
                        items = [(q, v) for (f, q), v in row.items()
                                 if family is None or f == family]
                        if items:
                            items.sort()
                            out.append((key, items))
                elif key_regexp:
                    # Selective regexp scans touch few rows: per-key
                    # merged reads beat extracting whole key ranges
                    # that the filter would then discard.
                    for key in keys[i:i + chunk]:
                        row = self._merged_row(table, key)
                        if not row:
                            continue
                        items = [(q, v) for (f, q), v in row.items()
                                 if family is None or f == family]
                        if items:
                            items.sort()
                            out.append((key, items))
                else:
                    # Tiered: RANGE-extract each generation once per
                    # chunk (two bisects + a sequential record walk)
                    # instead of probing every generation per key —
                    # per-key sst.get() was ~5 s of a 17 s cold 1-week
                    # scan over the 1B store (2.35M probes). Overlay
                    # order and tombstone semantics are exactly
                    # _merged_row's: generations oldest->newest, then
                    # frozen, then the live memtable; row tombstones
                    # mask all lower tiers.
                    ck = keys[i:i + chunk]
                    lo = ck[0]
                    hi = keys[i + chunk] if i + chunk < len(keys) \
                        else (stop or None)
                    t = self._table(table)
                    ft = self._frozen.get(table) if self._frozen \
                        else None
                    # Row tombstones suppress generation rows BEFORE
                    # the record decode (post-delete_row sweeps can
                    # mask many keys until the next full merge).
                    masked = t.row_tombs
                    if ft is not None and ft.row_tombs:
                        masked = masked | ft.row_tombs
                    merged: dict[bytes, dict] = {}
                    for sst in self._ssts:
                        if skip_paths and sst.path in skip_paths:
                            continue
                        for key, cells in sst.iter_rows_range(
                                table, lo, hi, skip=masked):
                            row = merged.get(key)
                            if row is None:
                                row = merged[key] = {}
                            for f, q, v in cells:
                                row[(f, q)] = v
                    if ft is not None:
                        for key in ft.range_keys(lo, hi):
                            if key in t.row_tombs:
                                continue
                            row = merged.get(key)
                            if row is None:
                                row = merged[key] = {}
                            for ckey, v in ft.rows[key].items():
                                if v is None:
                                    row.pop(ckey, None)
                                else:
                                    row[ckey] = v
                    live_get = t.rows.get
                    for key in ck:
                        row = merged.get(key)
                        lrow = live_get(key)
                        if lrow:
                            if row is None:
                                row = dict(lrow)
                            else:
                                for ckey, v in lrow.items():
                                    if v is None:
                                        row.pop(ckey, None)
                                    else:
                                        row[ckey] = v
                        if not row:
                            continue
                        if family is None:
                            items = [(q, v) for (f, q), v in row.items()
                                     if v is not None]
                        else:
                            items = [(q, v) for (f, q), v in row.items()
                                     if f == family and v is not None]
                        if items:
                            items.sort()
                            out.append((key, items))
            yield from out

    # -- atomics ----------------------------------------------------------

    def atomic_increment(self, table: str, key: bytes, family: bytes,
                         qualifier: bytes, amount: int = 1) -> int:
        """Increment an 8-byte big-endian counter cell, returning the new
        value (initialized from 0 like HBase's ICV)."""
        self._check_writable()
        with self._lock:
            row = self._merged_row(table, key)
            cur = row.get((family, qualifier)) if row else None
            value = (struct.unpack(">q", cur)[0] if cur else 0) + amount
            packed = struct.pack(">q", value)
            self.mutation_seq += 1
            self._wal_append(_OP_PUT, table.encode(), key, family, qualifier,
                             packed)
            self._apply_put(table, key, family, qualifier, packed)
            ticket = self._grp_ticket()
        self._wal_barrier(ticket)
        return value

    def compare_and_set(self, table: str, key: bytes, family: bytes,
                        qualifier: bytes, expected: bytes | None,
                        value: bytes) -> bool:
        """Atomic CAS: write only if the cell currently equals ``expected``
        (None = cell must not exist). Returns success."""
        self._check_writable()
        with self._lock:
            row = self._merged_row(table, key)
            cur = row.get((family, qualifier)) if row else None
            if cur != expected:
                return False
            self.mutation_seq += 1
            self._wal_append(_OP_PUT, table.encode(), key, family, qualifier,
                             value)
            self._apply_put(table, key, family, qualifier, value)
            ticket = self._grp_ticket()
        self._wal_barrier(ticket)
        return True
