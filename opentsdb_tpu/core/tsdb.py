"""TSDB — the thread-safe facade over storage, UIDs, and compaction.

Parity target: reference src/core/TSDB.java. Holds the KV store, the three
UID dictionaries (metrics/tagk/tagv, width 3), and the CompactionQueue; the
write path builds row keys, encodes values on their smallest width, and
schedules rows for compaction (:327-352).

TPU-first departures:
- ``add_chunk`` is the real ingest path: a decoded wire chunk of many
  series (``add_batch``: one series' columns, its one-series case) is
  sorted/deduped/encoded into one *pre-compacted* cell per (series,
  row-hour) and written as ONE put before it ever hits storage,
  eliminating the reference's write-then-compact amplification (one put
  per point + one rewrite per row per hour).
- ``read_row`` decodes cells straight into columnar arrays (codec_np), so
  queries never iterate cells point by point.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Iterator

import numpy as np

from opentsdb_tpu.core import codec, codec_np, tags as tags_mod
from opentsdb_tpu.core.compaction import CompactionQueue
from opentsdb_tpu.core.const import (MAX_TIMESPAN, TIMESTAMP_BYTES,
                                     UID_WIDTH)
from opentsdb_tpu.core.errors import NoSuchUniqueName, PleaseThrottleError
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.storage.kv import KVStore
from opentsdb_tpu.storage.sstable import series_hash
from opentsdb_tpu.uid.uniqueid import UniqueId
from opentsdb_tpu.utils.config import Config

LOG = logging.getLogger(__name__)

FAMILY = b"t"

# The write path's phases, a chunk at a time (add_chunk): timers whose
# sum_ms /stats carries; wal.append / wal.fsync run inside ingest.put.
_M_RESOLVE = _metrics.timer("ingest.resolve")
_M_ENCODE = _metrics.timer("ingest.encode")
_M_PUT = _metrics.timer("ingest.put")
_M_WINDOW = _metrics.timer("ingest.window")
_M_SKETCH = _metrics.timer("ingest.sketch")
_M_CHUNKS = _metrics.counter("ingest.chunks")
_M_CHUNK_SERIES = _metrics.counter("ingest.chunk.series")
_M_SERIES_COLD = _metrics.counter("ingest.series.cold")


class _Series:
    """A series as the write path resolved it (TSDB._resolve_series)."""

    __slots__ = ("metric", "tag_map", "metric_uid", "tmpl", "skey",
                 "tag_uids", "observed", "_label")

    def __init__(self, metric: str, tag_map: dict[str, str],
                 metric_uid: bytes, tmpl: bytes, skey: bytes,
                 tag_uids: list[tuple[bytes, bytes, bytes]]) -> None:
        self.metric = metric
        self.tag_map = tag_map
        self.metric_uid = metric_uid
        self.tmpl = tmpl            # row key at base time 0
        self.skey = skey
        self.tag_uids = tag_uids    # (metric, tagk, tagv) UIDs: the HLLs'
        self.observed = False       # tag_uids folded into the sketches
        self._label: str | None = None

    def label(self) -> str:
        """"metric{k=v,...}", the tenant accounts' name of the series."""
        if self._label is None:
            self._label = self.metric
            if self.tag_map:
                self._label += "{" + ",".join(
                    f"{k}={v}"
                    for k, v in sorted(self.tag_map.items())) + "}"
        return self._label


class TSDB:
    def __init__(self, store: KVStore, config: Config | None = None,
                 start_compaction_thread: bool = True) -> None:
        self.config = config or Config()
        self.store = store
        store.ensure_table(self.config.table)
        store.ensure_table(self.config.uidtable)
        self.table = self.config.table
        uidtable = self.config.uidtable
        self.metrics = UniqueId(store, uidtable, "metrics", 3)
        self.tagk = UniqueId(store, uidtable, "tagk", 3)
        self.tagv = UniqueId(store, uidtable, "tagv", 3)
        self.compactionq = CompactionQueue(
            self, start_thread=start_compaction_thread)
        # Write-side sstable codec (compress/): pushed onto the store
        # so checkpoint spills and compaction merges re-encode into
        # the configured format. Only a non-default config value
        # overrides a store the embedder configured directly; replicas
        # never spill, so the read side stays format-sniffed per file.
        codec = self.config.sstable_codec or "none"
        if codec != "none":
            if codec != "tsst4":
                raise ValueError(
                    f"unknown sstable_codec {codec!r} "
                    f"(one of: none, tsst4)")
            if hasattr(store, "sstable_codec"):
                store.sstable_codec = codec
        # WAL group commit (storage/kv.py): pushed onto the store the
        # same way; replicas never append so the knob is writer-only.
        group_ms = float(self.config.wal_group_ms or 0.0)
        if group_ms > 0 and hasattr(store, "wal_group_ms") \
                and not getattr(store, "read_only", False):
            store.wal_group_ms = group_ms
        # Spill-encode pipelining (storage/sstable.py module knob —
        # the writer pool is shared across stores/shards).
        from opentsdb_tpu.storage import sstable as _sstable_mod
        _sstable_mod.set_encode_workers(
            int(self.config.spill_encode_workers or 0))
        self._lock = threading.Lock()
        # Serializes checkpoint() end to end so the rollup tier's spill
        # bracketing (begin_spill ... fold_after_spill) pairs 1:1 with
        # an actual store spill. Without it, a manual checkpoint racing
        # the compaction thread's timer checkpoint gets rows=0 from the
        # store ("merge already in flight"), drains empty spill keys,
        # and then clears the CONCURRENT checkpoint's in-flight window
        # set and flips the tier state to ok while that spill is still
        # uncommitted — windows neither pending nor in-flight nor
        # folded, so stale summaries get served (and a crash in the gap
        # skips the rebuild).
        self._checkpoint_lock = threading.Lock()
        # Cluster write tier (cluster/): the epoch file this daemon's
        # store is governed by (None = not a cluster member). Set by
        # the CLI when --cluster is on; collect_stats exports the
        # current epoch as tsd.cluster.epoch so the self-monitoring
        # loop makes epoch SKEW between daemons alertable
        # (`tsdb check --skew`).
        self.cluster_epoch_path: str | None = None
        # Optional deregistration hook: the CLI's open-TSDB sweep list
        # (tools/cli._OPEN_TSDBS) sets this so shutdown() removes the
        # entry — embedders calling make_tsdb() outside main() would
        # otherwise accumulate hard references that pin closed stores
        # (and their memtables) against GC forever.
        self._deregister = None
        # ingest stats
        self.datapoints_added = 0
        # (metric, tags) -> _Series: what the write path resolved
        # (_resolve_series).
        self._series_cache: dict[tuple, _Series] = {}
        # Streaming sketch state (stats/livesketch.py): loaded from the
        # checkpoint snapshot when one exists (then re-folding only the
        # WAL-replayed memtable), else rebuilt from a full storage scan.
        self.sketches = None
        if self.config.enable_sketches:
            self._init_sketches()
        # Device-resident columnar hot window (storage/devstore.py):
        # ingest mirrors into HBM so queries skip the host->device
        # upload. CPU-oracle deployments skip it (nothing to upload to).
        self.devwindow = None
        # The boot refill's span (_warm_devwindow), as a dict: what a
        # restart spent loading the window, and how much it loaded.
        self.devwindow_refill: dict | None = None
        # A replica never ingests, so nothing would keep the window
        # (or its completeness bookkeeping) in sync with the writer's
        # appends arriving via store.refresh() — a boot-warmed window
        # would serve STALE resident answers while claiming coverage.
        # Replicas use the scan path. (Sketches stay: they reload on
        # every replica rebuild — reload_sketches() — so their lag is
        # bounded by the writer's checkpoint cadence + the poll.)
        # Checked locally, NOT written back into config: the Config
        # object is caller-owned and may be shared with a writer TSDB.
        use_devwindow = (self.config.device_window
                        and not getattr(store, "read_only", False))
        if use_devwindow and self.config.backend != "cpu":
            if self.config.devwindow_shards > 0:
                # Mesh-sharded hot set: logical shards round-robined
                # over the mesh devices (storage/devshard.py) so
                # capacity and stage throughput scale with mesh width.
                from opentsdb_tpu.storage.devshard import \
                    ShardedDeviceWindow

                self.devwindow = ShardedDeviceWindow(
                    devices=self._devwindow_devices(),
                    n_shards=self.config.devwindow_shards,
                    staging_points=self.config.device_window_staging,
                    max_points=self.config.device_window_points)
            else:
                from opentsdb_tpu.storage.devstore import DeviceWindow

                self.devwindow = DeviceWindow(
                    staging_points=self.config.device_window_staging,
                    max_points=self.config.device_window_points)
            self._warm_devwindow()
        # Materialized rollup tier (rollup/tier.py): daemons with a
        # persistent store only — an in-memory store never spills, so
        # every window would stay memtable-dirty and the planner could
        # never serve a summary. Writers own the fold and the tier's
        # state file; replicas open the same stores READ-ONLY
        # (ReadOnlyRollupTier) so the planner serves summaries on the
        # serve tier too, refreshed by refresh_replica().
        # Tenant cardinality control plane (opentsdb_tpu/tenant/):
        # per-tenant series accounting + heavy hitters + admission
        # limits, fed from this write path's series-identity hash.
        # Writers only — a replica neither admits nor snapshots.
        self.tenants = None
        self.tenant_limits = None
        if (self.config.tenant_accounting
                and not getattr(store, "read_only", False)):
            self._init_tenants()
        self.rollups = None
        if (self.config.enable_rollups
                and getattr(store, "_wal_path", None)):
            if getattr(store, "read_only", False):
                from opentsdb_tpu.rollup.tier import ReadOnlyRollupTier
                try:
                    self.rollups = ReadOnlyRollupTier(self, self.config)
                except Exception:
                    # A replica must come up even when the writer's
                    # tier is mid-rebuild/foreign: serve raw, let the
                    # refresh cycle adopt the tier when it settles.
                    LOG.exception("replica rollup tier unavailable; "
                                  "serving raw")
            else:
                from opentsdb_tpu.rollup.tier import RollupTier

                self.rollups = RollupTier(self, self.config)

    def _devwindow_devices(self):
        """The mesh device list the sharded hot set pins its shards to
        (mesh_shape when set, else all local devices). An unbuildable
        mesh raises: a sharded hot set that was asked for must not
        land whole on device 0."""
        import jax

        if self.config.mesh_shape:
            from opentsdb_tpu.parallel.plan import (
                build_mesh, flatten_series_mesh)
            mesh = flatten_series_mesh(
                build_mesh(self.config.mesh_shape))
            return list(mesh.devices.reshape(-1))
        return list(jax.local_devices())

    def _warm_devwindow(self) -> None:
        """Mirror pre-existing storage (WAL-replayed memtable + sstable
        tiers) into the device window so it covers history from before
        this process started, not just new ingest.

        Corrupt storage (conflicting duplicates — IllegalDataError, the
        fsck signal) disables the window outright: a partially-warmed
        window would claim coverage it doesn't have, and fsck must be
        able to run against exactly this data."""
        from opentsdb_tpu.core.errors import IllegalDataError
        from opentsdb_tpu.storage import devstore

        dw, points = self.devwindow, 0
        sp = obs_trace.Span("devwindow.refill")
        sp.start()
        runs = self._stored_block_runs()
        try:
            if runs is not None:
                # A store of columnar blocks is read as columns: a
                # block's rows of one metric-hour in one call, where
                # the scan below would frame them as rows again to
                # decode them one by one.
                for metric_uid, skeys, counts, ts, vals in runs:
                    dw.append_rows(metric_uid, skeys, counts, ts, vals)
                    points += len(ts)
            else:
                for key, cols in self.scan_columns(b"", b"\xff" * 64):
                    if len(cols.timestamps) == 0:
                        continue
                    pr = codec.parse_row_key(key)
                    dw.append(pr.metric_uid, codec.series_key(key),
                              cols.timestamps, cols.values)
                    points += len(cols.timestamps)
        except IllegalDataError:
            self.devwindow = None
        sp.stop()
        # Chunks cut so far: the last of a metric's points may still be
        # staged (a query of the metric uploads them).
        sp.tags.update(points=points, chunks=devstore.chunks_cut(dw),
                       columnar=runs is not None,
                       seconds=round(sp.ms / 1000.0, 3))
        if hasattr(dw, "shard_appended_points"):
            # A sharded window: the shards, and the points each took.
            took = dw.shard_appended_points()
            sp.tags.update(shards=len(took), shard_points=took)
        self.devwindow_refill = sp.to_dict()
        LOG.info("device window refilled: %d points in %d chunks, "
                 "%.1f s%s", points, sp.tags["chunks"], sp.ms / 1000.0,
                 f", a shard {sp.tags['shard_points']}"
                 if "shards" in sp.tags else "")

    def _stored_block_runs(self):
        """Every stored point of the data table in key order, a run of
        whole rows at a time, straight from TSST4 columnar blocks:
        (metric_uid, series keys, points a row, timestamps, values)
        for each run of a block's rows that share a metric and a base
        time (so the run's series are distinct). None where that could
        differ from ``scan_columns``, which then serves: rows in the
        memtable, a frozen tier or tombstones, a generation that is
        not format v4, generations whose key ranges overlap (a row may
        then have cells in two of them), or a block of the table that
        is not TSF32/TSINT (rows of several cells, annotations)."""
        from opentsdb_tpu.compress import codecs, fused
        from opentsdb_tpu.core.errors import IllegalDataError

        store, table = self.store, self.table
        if getattr(store, "encoded_range", None) is None \
                or getattr(store, "memtable_keys", None) is None \
                or store.memtable_keys(table):
            return None
        spans = store.encoded_range(table, b"", None)
        if not spans:
            return None
        plan, last = [], None
        for sst, lo, hi in spans:
            keys = sst._index[table][0]
            if last is not None and keys[lo] <= last:
                return None
            last = keys[hi - 1]
            for j in fused.block_range(sst, table, lo, hi):
                if sst.block_header(j)[0] not in (codecs.TSF32,
                                                  codecs.TSINT):
                    return None
                plan.append((sst, j))

        def runs():
            for sst, j in plan:
                b = codecs.parse_ts_block(sst.block_header(j)[0],
                                          sst.block_enc(j))
                ident = b.identity()
                if ident is None or b.table != table.encode():
                    raise IllegalDataError(
                        f"{sst.path}: block {j} holds no data rows")
                metric, base, skeys = ident
                qd, vals = b.columns()
                ts = base[b.rec_of_pt] + qd
                fused.prime(sst, table, j, b)
                cuts = np.flatnonzero(
                    (np.diff(metric) != 0) | (np.diff(base) != 0)) + 1
                edges = [0, *cuts.tolist(), b.n]
                for a, z in zip(edges[:-1], edges[1:]):
                    p0 = int(b.first_pt[a])
                    p1 = int(b.first_pt[z]) if z < b.n else b.P
                    yield (skeys[a][:UID_WIDTH], skeys[a:z],
                           b.npts[a:z], ts[p0:p1], vals[p0:p1])

        return runs()

    # ------------------------------------------------------------------
    # Streaming sketches
    # ------------------------------------------------------------------

    def _sketch_path(self) -> str | None:
        wal = getattr(self.store, "_wal_path", None)
        return wal + ".sketches" if wal else None

    def _init_sketches(self) -> None:
        import os as _os

        self._series_cache.clear()

        from opentsdb_tpu.stats.livesketch import LiveSketches

        path = self._sketch_path()
        cfg = self.config
        if path and _os.path.exists(path):
            self.sketches = LiveSketches.load(
                path, flush_points=cfg.sketch_flush_points)
            # The snapshot covers the sstable tier (committed in the
            # checkpoint window, before the WAL truncation); the live
            # memtable holds the WAL-replayed post-checkpoint writes —
            # re-fold only those, reading rows WITHOUT tier merging so
            # spilled cells aren't folded twice.
            keys = getattr(self.store, "memtable_keys", None)
            cells = getattr(self.store, "memtable_cells", None)
            if keys is not None and cells is not None:
                self._refold(
                    (k, self.read_row(k, cells(self.table, k, FAMILY)))
                    for k in keys(self.table))
                return
        else:
            self.sketches = LiveSketches(
                compression=cfg.sketch_compression,
                hll_p=cfg.sketch_hll_p,
                flush_points=cfg.sketch_flush_points)
            if not getattr(self.store, "memtable_keys", None):
                return
        # No snapshot (or unknown store shape): rebuild from everything.
        self._refold(self.scan_columns(b"", b"\xff" * 64))

    def refresh_replica(self) -> bool:
        """One full replica catch-up cycle: raw store refresh (WAL
        suffix replay, or a rebuild when the writer checkpointed),
        sketch snapshot reload when a rebuild happened, then the
        read-only rollup tier — in THAT order, which is what makes
        replica-served rollup answers safe (ReadOnlyRollupTier's
        docstring carries the proof). The compaction timer (legacy
        --read-only daemons) and the serve tier's WalTailer both
        drive this. Returns True when the raw view changed."""
        if not getattr(self.store, "read_only", False):
            raise ValueError("refresh_replica() is for read-only "
                             "replica stores")
        before = getattr(self.store, "rebuilds", 0)
        changed = self.store.refresh()
        if getattr(self.store, "rebuilds", 0) != before:
            self.reload_sketches()
        tier = self.rollups
        if (tier is None and self.config.enable_rollups
                and getattr(self.store, "_wal_path", None)):
            # Construction failed at boot (writer mid-rebuild, torn
            # state file): keep trying each cycle so the tier is
            # adopted once the writer settles — a replica must not
            # serve raw forever over a transient boot race.
            from opentsdb_tpu.rollup.tier import ReadOnlyRollupTier
            try:
                self.rollups = tier = ReadOnlyRollupTier(self,
                                                         self.config)
            except Exception as e:
                LOG.debug("replica rollup tier still unavailable: %r",
                          e)
        if tier is not None and getattr(tier, "read_only", False):
            tier.refresh()
        return changed

    def promote(self, writer_epoch: int, epoch_guard=None) -> None:
        """Replica → writer takeover (the cluster failover's storage
        half; cluster/promote.py and the ``/promote`` endpoint drive
        it). The caller has already bumped the persisted epoch.

        Order matters: the store takes ownership first (fresh-inode
        WAL + epoch header, storage/kv.promote_writable), then the
        sketch state re-initializes in WRITER mode (snapshot load +
        memtable re-fold — the boot path), then the read-only rollup
        view swaps for the owning tier (adopting ROLLUP.json; a tier
        the dead writer left mid-fold rebuilds through the standard
        pending-marker catch-up). The store + sketch swap runs under
        the checkpoint lock; the rollup tier swap runs OUTSIDE it
        (lock discipline below). The device window stays off — a
        replica never had one, and a promoted writer serves through
        the scan path until its next restart."""
        with self._checkpoint_lock:
            self.store.promote_writable(writer_epoch,
                                        epoch_guard=epoch_guard)
            try:
                if self.config.enable_sketches:
                    self._init_sketches()
                if self.config.tenant_accounting:
                    # The promoted writer owns admission now: adopt
                    # the dead writer's TENANTS.json (or rebuild).
                    self._init_tenants()
                old = self.rollups
                self.rollups = None
            except BaseException:
                # The store already committed its takeover; a failure
                # in the post-store steps (torn sketch snapshot, EIO)
                # must not leave a HALF-promoted daemon — writable
                # store + bumped epoch but role still replica, which
                # would make a retried /promote short-circuit on
                # "already writer" over broken serving state. Demote
                # the store back so the caller's recovery (re-attach a
                # tailer, let the router try the next candidate) acts
                # on a genuine replica.
                self.tenants = None
                self.tenant_limits = None
                try:
                    self.store.demote_readonly()
                except Exception:
                    LOG.exception("rollback demote after failed "
                                  "promotion")
                raise
        # Rollup tier swap OUTSIDE the checkpoint lock — the same
        # discipline shutdown() documents: close() joins the tier's
        # catch-up thread, and the rebuild-completion commit takes
        # THIS lock (sync catch-up takes it in the constructor), so
        # doing either under it deadlocks. The window is safe in the
        # daemon flow: a promoting replica's compaction timer has
        # checkpoint_interval 0 until _do_promote restores it after
        # this returns, so no spill can race the tier-less gap.
        if old is not None:
            try:
                old.close()
            except Exception:
                LOG.exception("closing replica rollup view during "
                              "promotion")
        if (self.config.enable_rollups
                and getattr(self.store, "_wal_path", None)):
            from opentsdb_tpu.rollup.tier import RollupTier
            try:
                self.rollups = RollupTier(self, self.config)
            except Exception:
                # The promoted writer must SERVE even when the old
                # writer's tier is torn; raw answers stay exact and
                # the operator sees rollup.ready=0.
                LOG.exception("promoted writer rollup tier "
                              "unavailable; serving raw")

    def demote(self) -> None:
        """Writer → tailing replica, in place (a deposed writer that
        came back and was told so). The owning rollup tier closes
        BEFORE the store flips — its catch-up thread reads the raw
        store — then the store drops WAL + flock and rebuilds through
        the replica recovery path, sketches reload from the (new)
        writer's snapshot, and the read-only rollup view is adopted
        exactly as a replica boot would."""
        # The owning tier closes FIRST and OUTSIDE the checkpoint lock
        # (the shutdown() discipline): close() joins the catch-up
        # thread, which acquires this very lock for its completion
        # commit — joining it while holding the lock deadlocks the
        # daemon inside /demote. Detach the tier before closing so no
        # concurrent checkpoint brackets a spill against a
        # half-closed tier.
        with self._checkpoint_lock:
            old = self.rollups
            self.rollups = None
        if old is not None:
            try:
                old.close()
            except Exception:
                LOG.exception("closing rollup tier during demotion")
        with self._checkpoint_lock:
            # Queued row compactions are writer work: a demoted daemon
            # would only log ReadOnlyStoreError noise trying to write
            # them back. They're reconstructible soft state — the new
            # writer re-queues and compacts as it reads.
            with self.compactionq._lock:
                self.compactionq._queue.clear()
            self.store.demote_readonly()
            self.reload_sketches()
            # A replica neither admits nor snapshots tenant state —
            # the new writer owns TENANTS.json now.
            self.tenants = None
            self.tenant_limits = None
        if (self.config.enable_rollups
                and getattr(self.store, "_wal_path", None)):
            from opentsdb_tpu.rollup.tier import ReadOnlyRollupTier
            try:
                self.rollups = ReadOnlyRollupTier(self, self.config)
            except Exception:
                # refresh_replica retries adoption every cycle.
                LOG.exception("demoted daemon rollup view "
                              "unavailable; serving raw")

    def reload_sketches(self) -> None:
        """Replica catch-up: re-load the writer's sketch snapshot and
        re-fold the (freshly rebuilt) memtable on top. The refresh
        timer calls this whenever store.refresh() REBUILT — which
        happens on every writer checkpoint — so replica sketch lag is
        bounded by the writer's checkpoint cadence plus the poll
        interval (suffix replays between checkpoints are not folded;
        re-folding the whole memtable per poll would be O(window)
        every few seconds). Queries racing the swap keep a coherent
        reference to the previous sketch set."""
        if self.config.enable_sketches:
            self._init_sketches()

    def _refold(self, rows) -> None:
        for key, cols in rows:
            if len(cols.timestamps) == 0:
                continue
            pr = codec.parse_row_key(key)
            self.sketches.observe(
                codec.series_key(key), cols.values,
                [(pr.metric_uid, k, v) for k, v in pr.tag_uids])
        self.sketches.flush()

    # ------------------------------------------------------------------
    # Tenant cardinality control plane (opentsdb_tpu/tenant/)
    # ------------------------------------------------------------------

    def _tenants_path(self) -> str | None:
        """TENANTS.json next to the WAL: inside the store directory
        for sharded stores (the SHARDS.json/EPOCH.json convention),
        ``<wal>.tenants.json`` for a single-file WAL (several single
        stores may share one directory in tests)."""
        wal = getattr(self.store, "_wal_path", None)
        if not wal:
            return None
        from opentsdb_tpu.tenant.accounting import STATE_NAME
        if getattr(self.store, "shard_count", None) is not None:
            # The sharded store's _wal_path is its <dir>/store naming
            # root (not a real directory); the snapshot lives beside
            # SHARDS.json at the store root.
            return os.path.join(os.path.dirname(wal), STATE_NAME)
        return wal + ".tenants.json"

    def _init_tenants(self) -> None:
        """Boot (or promotion) path: load the snapshot and re-fold the
        WAL-replayed memtable's series on top — the snapshot commits
        BEFORE each spill, so it always covers the sstable tier and
        the memtable delta is everything it can be missing. A torn or
        foreign state file rebuilds from a full storage scan instead
        (totals exact; per-tenant splits land on the default tenant,
        declared via recovered_series)."""
        from opentsdb_tpu.tenant.accounting import TenantAccountant
        from opentsdb_tpu.tenant.limits import (TenantLimiter,
                                                parse_overrides)

        self._series_cache.clear()
        cfg = self.config
        self.tenant_limits = TenantLimiter(
            max_series=cfg.tenant_max_series,
            global_max=cfg.tenant_global_max_series,
            mode=cfg.tenant_limit_mode,
            overrides=parse_overrides(cfg.tenant_overrides))
        path = self._tenants_path()
        acct = None
        if path and os.path.exists(path):
            try:
                acct = TenantAccountant.load(
                    path, exact_cutoff=cfg.tenant_exact_cutoff,
                    hll_p=cfg.tenant_hll_p, topk=cfg.tenant_topk)
            except Exception as e:
                LOG.warning("TENANTS.json at %s torn/foreign (%r); "
                            "rebuilding tenant accounting from "
                            "storage", path, e)
        if acct is not None:
            # Delta fold: only series the WAL replayed past the
            # snapshot (the sketches _init_sketches discipline).
            keys = getattr(self.store, "memtable_keys", None)
            if keys is not None:
                acct.fold_recovered(
                    series_hash(codec.series_key(k))
                    for k in keys(self.table))
            else:
                acct.fold_recovered(self._storage_series_hashes())
        else:
            torn = bool(path and os.path.exists(path))
            acct = TenantAccountant(
                path=path, exact_cutoff=cfg.tenant_exact_cutoff,
                hll_p=cfg.tenant_hll_p, topk=cfg.tenant_topk)
            if torn or self.tenant_limits.enabled:
                # The full scan is semantically REQUIRED under
                # enforcement (the limiter must never refuse a
                # pre-existing series as "new"), and a torn snapshot
                # means accounting was live here — recover it exactly.
                acct.fold_recovered(self._storage_series_hashes())
            else:
                # Observability-only mode on a store with no snapshot
                # (first boot, or a pre-tenancy store upgrading):
                # don't block the constructor on a full raw-storage
                # scan nobody's limits need. No snapshot also means
                # no checkpoint ever committed, so any stored rows
                # live in the WAL-replayed memtable — fold just that
                # delta (sstable-backed stores only lack a snapshot
                # on upgrade, where counts re-attribute to their REAL
                # tenants as series next ingest and the first
                # checkpoint makes this a one-time transition).
                keys = getattr(self.store, "memtable_keys", None)
                if keys is not None:
                    acct.fold_recovered(
                        series_hash(codec.series_key(k))
                        for k in keys(self.table))
            acct.rebuilt = torn
        self.tenants = acct

    def _storage_series_hashes(self):
        """Every distinct series-identity hash currently in storage
        (raw key scan, no cell decode) — the rebuild source when the
        snapshot is gone."""
        seen: set[int] = set()
        for key, _items in self.store.scan_raw(self.table, b"",
                                               b"\xff" * 64):
            h = series_hash(codec.series_key(key))
            if h not in seen:
                seen.add(h)
                yield h

    def _admit_series(self, tenant: str, skey: bytes,
                      metric: str) -> None:
        """Tenant admission + accounting for one about-to-be-written
        series; raises TenantLimitError (enforce mode) when the series
        is NEW and the tenant (or the directory) is over budget.
        Counting happens here, BEFORE the storage put, mirroring the
        sketch directory's note_series placement: over-counting a
        series whose put then fails hard is harmless and bounded by
        the error count, while counting after would let a throttled
        partial batch leave stored rows that look refusable forever."""
        acct = self.tenants
        if acct is None:
            return
        h = series_hash(skey)
        if acct.seen(h):
            return
        self.tenant_limits.admit_new_series(acct, tenant)
        acct.note_new_series(tenant, h, metric)

    # ------------------------------------------------------------------
    # Row-key construction
    # ------------------------------------------------------------------

    def resolve_tags(self, tag_map: dict[str, str],
                     create: bool = True) -> list[tuple[bytes, bytes]]:
        """Resolve tag names/values to UID pairs, sorted by tagk id.

        Sorting by the tag *name UID* matches the reference's
        resolveOrCreateAll + sort (Tags.java:308-348): row keys for one
        logical series are byte-identical regardless of input order.
        """
        get_k = self.tagk.get_or_create_id if create else self.tagk.get_id
        get_v = self.tagv.get_or_create_id if create else self.tagv.get_id
        pairs = [(get_k(k), get_v(v)) for k, v in tag_map.items()]
        pairs.sort()
        return pairs

    def _row_parts(self, metric: str, tag_map: dict[str, str],
                   create_metric: bool | None = None,
                   create_tags: bool = True,
                   ) -> tuple[bytes, list[tuple[bytes, bytes]]]:
        """(metric_uid, sorted tag UID pairs) for a series — the resolved
        parts row_key_for assembles, exposed so the write path can reuse
        them (sketch folds) without re-parsing the key it just built."""
        tags_mod.check_metric_and_tags(metric, tag_map)
        if create_metric is None:
            create_metric = self.config.auto_create_metrics
        metric_uid = (self.metrics.get_or_create_id(metric) if create_metric
                      else self.metrics.get_id(metric))
        return metric_uid, self.resolve_tags(tag_map, create_tags)

    def _row_parts_admitted(self, tenant: str, metric: str,
                            tag_map: dict[str, str],
                            ) -> tuple[bytes, list[tuple[bytes, bytes]]]:
        """``_row_parts`` behind the tenant gate. With enforcement on,
        resolve WITHOUT creating first: a missing UID means the series
        is certainly NEW, so the tenant/global budget check runs
        before ``get_or_create`` allocates durable UID mappings — a
        refused series must not grow the metric/tagk/tagv maps, since
        that growth is exactly the resource the limiter protects. When
        every UID resolves the combination may still be new, but the
        probe minted nothing and ``_admit_series`` settles it against
        the seen-set once the series hash exists."""
        if (self.tenants is None or not self.tenant_limits.enabled
                or self.tenant_limits.mode != "enforce"):
            return self._row_parts(metric, tag_map)
        try:
            return self._row_parts(metric, tag_map, create_metric=False,
                                   create_tags=False)
        except NoSuchUniqueName:
            if not self.config.auto_create_metrics:
                # The metric itself may be the missing piece, and it
                # can never be created here — that put dies as
                # "unknown metric" regardless of any budget, so it
                # must not masquerade as (or count toward) a tenant
                # refusal. Re-raises NoSuchUniqueName if so.
                self.metrics.get_id(metric)
            self.tenant_limits.admit_new_series(self.tenants, tenant)
            return self._row_parts(metric, tag_map)

    def row_key_for(self, metric: str, tag_map: dict[str, str],
                    base_ts: int, create_metric: bool | None = None,
                    create_tags: bool = True) -> bytes:
        metric_uid, pairs = self._row_parts(metric, tag_map,
                                            create_metric, create_tags)
        return codec.row_key(metric_uid, base_ts, pairs)

    # ------------------------------------------------------------------
    # Write path
    # ------------------------------------------------------------------

    def add_point(self, metric: str, timestamp: int, value: int | float,
                  tag_map: dict[str, str], durable: bool = True,
                  tenant: str = "default") -> None:
        """Store one data point (reference TSDB.addPoint :236-352)."""
        if timestamp & ~0xFFFFFFFF:
            raise ValueError(
                f"{'negative' if timestamp < 0 else 'bad'} "
                f"timestamp={timestamp} when trying to add value={value} "
                f"to metric={metric}, tags={tag_map}")
        if isinstance(value, bool):
            raise ValueError("boolean value")
        if isinstance(value, float):
            buf, flags = codec.encode_float(value)
        else:
            buf, flags = codec.encode_long(value)
        base_ts = codec.base_time(timestamp)
        # Resolved, admitted and registered in the sketch directory
        # before the put, as a chunk's series are (_resolve_series).
        s = self._resolve_series(tenant, metric, tag_map)
        row = (s.tmpl[:UID_WIDTH] + base_ts.to_bytes(TIMESTAMP_BYTES, "big")
               + s.tmpl[UID_WIDTH + TIMESTAMP_BYTES:])
        qual = codec.encode_qualifier(timestamp - base_ts, flags)
        self.store.put(self.table, row, FAMILY, qual, buf, durable=durable)
        # Scalar puts bypass the delta-fold feed (add_chunk): their
        # coarse window must fall back to the full fold rescan.
        delta = getattr(self.rollups, "delta", None)
        if delta is not None:
            delta.invalidate(s.skey, base_ts)
        if self.config.enable_compactions:
            self.compactionq.add(row)
        self.datapoints_added += 1
        if self.tenants is not None:
            self.tenants.note_points(tenant, s.label(), 1)
        if self.sketches is not None:
            self.sketches.observe(s.skey, np.asarray([value], np.float64),
                                  s.tag_uids)
        if self.devwindow is not None:
            self.devwindow.append(s.metric_uid, s.skey,
                                  np.asarray([timestamp], np.int64),
                                  np.asarray([value], np.float32))

    def add_batch(self, metric: str, timestamps: np.ndarray,
                  values: np.ndarray, tag_map: dict[str, str],
                  durable: bool = True,
                  is_float: np.ndarray | None = None,
                  int_values: np.ndarray | None = None,
                  tenant: str = "default", sync: bool = True) -> int:
        """Columnar ingest for one series: pre-compacted cell per row-hour
        (the one-series case of ``add_chunk``).

        ``values`` may be an integer or floating dtype; float points are
        stored as 4-byte floats (matching telnet ingest), int points on
        their smallest widths. Pass ``is_float`` to type points
        individually within a float-dtyped ``values`` array (mixed series,
        like per-line telnet/import ingest produces) — and ``int_values``
        (int64) alongside it to keep integers above 2^53 exact, since
        float64 cannot represent them. ``sync=False`` skips the per-call
        WAL group-commit barrier so a multi-series caller can batch many
        series under one covering ``store.wal_barrier()`` before acking
        (no-op when group commit is off). Returns the points written.
        """
        timestamps = np.asarray(timestamps, dtype=np.int64)
        if timestamps.size == 0:
            return 0
        if is_float is not None:
            fmask = np.asarray(is_float, dtype=bool)
            fvals = np.asarray(values, dtype=np.float64)
            if int_values is not None:
                ivals = np.asarray(int_values, dtype=np.int64)
            else:
                ivals = np.where(fmask, 0, fvals).astype(np.int64)
        elif np.issubdtype(np.asarray(values).dtype, np.floating):
            fvals = np.asarray(values, dtype=np.float64)
            ivals = np.zeros_like(timestamps)
            fmask = np.ones(timestamps.shape, dtype=bool)
        else:
            ivals = np.asarray(values, dtype=np.int64)
            fvals = ivals.astype(np.float64)
            fmask = np.zeros(timestamps.shape, dtype=bool)
        n, errors = self.add_chunk(
            ((metric, tag_map),), None, timestamps, fvals, ivals, fmask,
            durable=durable, tenant=tenant, sync=sync)
        if errors:
            raise errors[0]
        return n

    # Resolved series kept by (metric, tags): see _resolve_series.
    _SERIES_CACHE_CAP = 1 << 18

    def _resolve_series(self, tenant: str, metric: str,
                        tag_map: dict[str, str]) -> "_Series":
        """A series' metric UID, row-key template and series key. One
        seen before is a dict probe; a new one is resolved behind the
        tenant gate, admitted and registered in the sketch directory
        (all of which may raise, for this series alone) and kept.

        What is kept never goes stale while the objects it was checked
        against live: UIDs are assigned once, an admitted series stays
        in the accountant's seen-set, a registered one in the sketch
        directory. ``_init_sketches`` / ``_init_tenants`` replace those
        objects and empty the cache; so does ``drop_caches``, the
        operator's way to make a TSD forget a name mapping that was
        changed out of band."""
        key = (metric, frozenset(tag_map.items()))
        s = self._series_cache.get(key)
        if s is not None:
            return s
        _M_SERIES_COLD.inc()
        metric_uid, pairs = self._row_parts_admitted(tenant, metric,
                                                     tag_map)
        tmpl = bytes(codec.row_key(metric_uid, 0, pairs))
        skey = codec.series_key(tmpl)
        # Tenant admission precedes both the directory registration
        # and the put: a NEW series from an over-budget tenant refuses
        # here (TenantLimitError, declared on the wire) before any
        # byte lands — existing series pass the seen-set check and
        # keep ingesting regardless of the tenant's budget.
        self._admit_series(tenant, skey, metric)
        # The series enters the sketch slot DIRECTORY before any row
        # becomes visible in storage: the executor's bloom-pruning
        # hint treats the directory as a complete superset of series
        # with stored data, and registering after the put would leave
        # a window where a concurrent query prunes the shard holding
        # this series' first rows. (Values fold after the put as
        # before; over-registering an unapplied series is harmless.)
        if self.sketches is not None:
            self.sketches.note_series(skey)
        s = _Series(metric, tag_map, metric_uid, tmpl, skey,
                    [(metric_uid, k, v) for k, v in pairs])
        if len(self._series_cache) >= self._SERIES_CACHE_CAP:
            self._series_cache.clear()
        self._series_cache[key] = s
        return s

    def add_chunk(self, series, sid: np.ndarray | None,
                  timestamps: np.ndarray, fvalues: np.ndarray,
                  ivalues: np.ndarray, is_float: np.ndarray,
                  durable: bool = True, tenant: str = "default",
                  sync: bool = True) -> tuple[int, dict[int, Exception]]:
        """Columnar ingest of a decoded chunk: many series, one put.

        ``series[s]`` is a (metric, tags) pair and ``sid[i]`` the series
        of point i (None: every point is of ``series[0]``); the value
        columns are ``add_batch``'s, typed a point. The chunk is sorted
        and deduplicated by (series, timestamp) once, every (series,
        row-hour) cell encoded in one pass, and written with ONE
        ``put_many_columnar`` a row-key length: one WAL record, applied
        whole or not at all on replay; then one device-window append a
        metric and one sketch observation.

        Series stay independent: one that cannot be written (a
        conflicting duplicate, an unknown metric, a tenant over its
        limit) is left out with its exception in the returned dict, by
        series index, and the rest land. An exception of the put itself
        (a fenced writer, a throttle) is every series' of that put; on a
        throttle the rows that did apply are queued for compaction and
        every metric of the chunk drops its device window, since which
        rows landed is unknowable from here. Returns (points written,
        errors)."""
        errors: dict[int, Exception] = {}
        ts = np.asarray(timestamps, dtype=np.int64)
        if ts.size == 0:
            return 0, errors
        with _M_RESOLVE.time():
            if (ts & ~np.int64(0xFFFFFFFF)).any():
                err = ValueError("timestamp out of range in batch")
                if sid is None:
                    return 0, {0: err}
                off = (ts & ~np.int64(0xFFFFFFFF)) != 0
                errors.update((s, err)
                              for s in np.unique(sid[off]).tolist())
                keep = ~np.isin(sid, list(errors))
                sid, ts, fvalues, ivalues, is_float = (
                    sid[keep], ts[keep], fvalues[keep], ivalues[keep],
                    is_float[keep])
            # One vectorized pass for the whole chunk: sort + dedup by
            # (series, timestamp); same-timestamp points are same-hour
            # by definition.
            sid_s, ts_s, f_s, i_s, m_s, bad = codec_np.sort_dedup_multi(
                sid, ts, fvalues, ivalues, is_float)
            for s, at in bad.items():
                errors[s] = codec_np.duplicate_data_error(at)
            # Each distinct series, resolved once; ``local`` numbers the
            # resolved ones 0.. in series order.
            resolved: list[_Series] = []
            sid_of: list[int] = []      # local number -> series index
            if sid_s is None:
                distinct = (0,) if len(ts_s) else ()
            else:
                distinct = np.unique(sid_s).tolist()
                local = np.full(len(series), -1, np.int64)
            for s in distinct:
                metric, tag_map = series[s]
                try:
                    r = self._resolve_series(tenant, metric, tag_map)
                except Exception as e:
                    errors[s] = e
                    continue
                if sid_s is not None:
                    local[s] = len(resolved)
                resolved.append(r)
                sid_of.append(s)
            # ``loc``: the local number of each point's series; None
            # where one series is left. That is add_batch's case (a
            # series' whole span a call, 40,000 calls a store build):
            # from here on it runs the same steps on scalars, without
            # the arrays that tell a chunk's series apart.
            loc = None
            if sid_s is not None and len(resolved):
                loc = local[sid_s]
                if len(errors):
                    keep = loc >= 0
                    loc, ts_s, f_s, i_s, m_s = (
                        loc[keep], ts_s[keep], f_s[keep], i_s[keep],
                        m_s[keep])
                if len(resolved) == 1:
                    loc = None
        _M_CHUNKS.inc()
        _M_CHUNK_SERIES.inc(len(resolved))
        if not resolved or len(ts_s) == 0:
            return 0, errors
        with _M_ENCODE.time():
            # All (series, row-hour) cells in one flat-buffer pass, and
            # all row keys in one (_key_blobs).
            base = ts_s - ts_s % MAX_TIMESPAN
            row_starts = self._row_starts(base, loc)
            quals, vals = codec_np.encode_cells_multi(
                ts_s - base, f_s, i_s, m_s, row_starts)
            row_loc = None if loc is None else loc[row_starts]
            puts = self._key_blobs(resolved, row_loc, base[row_starts])
        # Rows that already held cells BEFORE the put become multi-cell
        # and must be queued so the per-batch compacted cells merge into
        # one; the store reports that per row in a single locked pass.
        delta = getattr(self.rollups, "delta", None)
        existed_of_row = np.zeros(len(row_starts), bool)
        failed: list[int] = []     # local numbers whose put raised
        with _M_PUT.time():
            for L, rows, kb in puts:
                q = quals if rows is None else [quals[r] for r in rows]
                v = vals if rows is None else [vals[r] for r in rows]
                try:
                    existed = self.store.put_many_columnar(
                        self.table, FAMILY, kb, L, q, v, durable=durable,
                        sync=sync)
                except Exception as e:
                    # Every series of this put, and where its rows lie.
                    at = (np.arange(len(row_starts)) if rows is None
                          else np.asarray(rows))
                    of = (np.zeros(len(at), np.int64) if row_loc is None
                          else row_loc[at])
                    group = np.unique(of).tolist()
                    failed += group
                    for j in group:
                        errors[sid_of[j]] = e
                    if not isinstance(e, PleaseThrottleError):
                        continue
                    # Which rows landed is unknowable from here; the
                    # chunk's rollup windows can no longer be folded
                    # incrementally.
                    if delta is not None:
                        for j in group:
                            delta.kill_batch(resolved[j].skey,
                                             base[row_starts[at[of == j]]])
                    # A mid-batch throttle still queues the rows that
                    # DID apply.
                    existed = getattr(e, "partial_existed", [])
                    # Rows that DID apply are now in storage but will
                    # never be appended to the device window, and a
                    # later retry of the chunk would fail its
                    # monotonicity check anyway — drop the windows so
                    # queries fall back to the scan path instead of
                    # silently serving a partial view.
                    if self.devwindow is not None:
                        for uid in {r.metric_uid for r in resolved}:
                            self.devwindow.invalidate(uid)
                # any() is a C-level scan: the sustained-ingest shape is
                # all-new rows, where enumerating millions of False
                # flags per batch would cost more than the batch's dict
                # inserts.
                if any(existed):
                    had = np.flatnonzero(existed)
                    had_rows = had if rows is None else np.asarray(rows)[had]
                    existed_of_row[had_rows] = True
                    if self.config.enable_compactions:
                        self.compactionq.add_many(
                            [kb[i * L:(i + 1) * L] for i in had.tolist()],
                            base[row_starts[had_rows]].tolist())
        if failed:
            if len(failed) == len(resolved):
                return 0, errors
            # The series of a put that failed take no further part.
            gone = np.zeros(len(resolved), bool)
            gone[failed] = True
            keep = ~gone[loc]
            existed_of_row = existed_of_row[~gone[row_loc]]
            renum = np.cumsum(~gone) - 1
            resolved = [r for r, g in zip(resolved, gone) if not g]
            loc, ts_s, f_s, i_s, m_s, base = (
                None if len(resolved) == 1 else renum[loc[keep]],
                ts_s[keep], f_s[keep], i_s[keep], m_s[keep], base[keep])
            row_starts = self._row_starts(base, loc)
            row_loc = None if loc is None else loc[row_starts]
        n = len(ts_s)
        # How many points each series has (sorted by series: one run
        # each).
        counts = ([n] if loc is None else
                  np.bincount(loc, minlength=len(resolved)).tolist())
        # Rollup delta accumulators (rollup/delta.py): the applied
        # chunk's columns ARE what a checkpoint fold's raw rescan
        # would decode, so buffer them for the incremental fold path.
        if delta is not None:
            ends = np.cumsum(counts)
            row_of_series = ([0, len(row_starts)] if row_loc is None else
                             np.searchsorted(row_loc,
                                             np.arange(len(resolved) + 1)))
            for j, r in enumerate(resolved):
                a, b = int(ends[j] - counts[j]), int(ends[j])
                ra, rb = row_of_series[j], row_of_series[j + 1]
                delta.feed(r.skey, ts_s[a:b], f_s[a:b], i_s[a:b],
                           m_s[a:b], base[a:b], row_starts[ra:rb] - a,
                           existed_of_row[ra:rb].tolist())
        self.datapoints_added += n
        if self.tenants is not None:
            self.tenants.note_points_many(
                tenant, [r.label() for r in resolved], counts)
        # Sketch fold covers fully applied puts only; values as stored,
        # floats and ints alike. One float32 conversion shared by both
        # consumers (the digests quantize to f32 anyway; the window
        # stores f32).
        if self.sketches is not None or self.devwindow is not None:
            f32 = f_s.astype(np.float32)
            if self.sketches is not None:
                with _M_SKETCH.time():
                    # A series' tag values fold into the HLLs the first
                    # time its points apply; register max is idempotent,
                    # so later chunks have nothing to add.
                    fresh = [r for r in resolved if not r.observed]
                    self.sketches.observe_many(
                        [r.skey for r in resolved], loc, f32,
                        [t for r in fresh for t in r.tag_uids])
                    for r in fresh:
                        r.observed = True
            if self.devwindow is not None:
                with _M_WINDOW.time():
                    self._append_window(resolved, loc, ts_s, f32)
        return n, errors

    @staticmethod
    def _key_blobs(resolved, row_loc: np.ndarray | None,
                   row_base: np.ndarray) -> list:
        """The row keys of a chunk's rows, one CONTIGUOUS blob a
        row-key length (it flows into put_many_columnar and on into the
        WAL record as-is): ``(length, rows, blob)`` with ``rows`` the
        rows of that length, None for every row. Row r is of series
        ``resolved[row_loc[r]]`` (None: all of ``resolved[0]``): its
        template with the base-time bytes stamped."""
        stamp = row_base.astype(">u4").view(np.uint8).reshape(-1, 4)
        when = slice(UID_WIDTH, UID_WIDTH + TIMESTAMP_BYTES)
        if row_loc is None:
            tmpl = resolved[0].tmpl
            keys = np.tile(np.frombuffer(tmpl, np.uint8), (len(stamp), 1))
            keys[:, when] = stamp
            return [(len(tmpl), None, keys.tobytes())]
        key_len = np.fromiter((len(r.tmpl) for r in resolved), np.int64,
                              len(resolved))
        puts = []
        for L in np.unique(key_len).tolist():
            members = np.flatnonzero(key_len == L)
            whole = len(members) == len(resolved)
            rows = None if whole else np.flatnonzero(key_len[row_loc] == L)
            tmpls = np.frombuffer(
                b"".join(resolved[j].tmpl for j in members.tolist()),
                np.uint8).reshape(-1, L)
            keys = tmpls[row_loc if whole else
                         np.searchsorted(members, row_loc[rows])]
            keys[:, when] = stamp if whole else stamp[rows]
            puts.append((L, None if whole else rows.tolist(),
                         keys.tobytes()))
        return puts

    @staticmethod
    def _row_starts(base: np.ndarray, loc: np.ndarray | None) -> np.ndarray:
        """Where a (series, row-hour) run of points sorted by (series,
        timestamp) begins; ``loc`` None is one series."""
        brk = base[1:] != base[:-1]
        if loc is not None:
            brk |= loc[1:] != loc[:-1]
        return np.concatenate(([0], np.flatnonzero(brk) + 1))

    def _append_window(self, resolved, loc, ts_s, f32) -> None:
        """The applied chunk into the device window: one append a
        metric (points are sorted by series, so a metric's series are
        runs; metrics interleave only where a chunk's series do)."""
        dw = self.devwindow
        if len(resolved) == 1:
            dw.append(resolved[0].metric_uid, resolved[0].skey, ts_s, f32)
            return
        uids: dict[bytes, int] = {}
        metric_of = np.fromiter(
            (uids.setdefault(r.metric_uid, len(uids)) for r in resolved),
            np.int64, len(resolved))
        if len(uids) == 1:
            dw.append_many(resolved[0].metric_uid,
                           [r.skey for r in resolved], loc, ts_s, f32)
            return
        metric_of_pt = metric_of[loc]
        renum = np.zeros(len(resolved), np.int64)
        for uid, m in uids.items():
            members = np.flatnonzero(metric_of == m)
            pts = np.flatnonzero(metric_of_pt == m)
            renum[members] = np.arange(len(members))
            dw.append_many(uid, [resolved[j].skey for j in members],
                           renum[loc[pts]], ts_s[pts], f32[pts])

    # ------------------------------------------------------------------
    # Compaction
    # ------------------------------------------------------------------

    def compact_row(self, key: bytes) -> None:
        """Merge all cells of a row into one compacted cell in storage.

        Parity: reference CompactionQueue.compact (:243-437) — single-cell
        rows are left alone (modulo the legacy float fix), the merged cell
        is written before the originals are deleted, and an original cell
        that already equals the merged form is never deleted-after-write.
        """
        delta = getattr(self.rollups, "delta", None)
        if delta is None:
            self._compact_row(key)
            return
        # Compaction preserves the row's point set: mark this thread's
        # deletes as preserving so the store delete hook doesn't kill
        # the row's rollup delta window (rollup/delta.py).
        delta.preserve.on = True
        try:
            self._compact_row(key)
        finally:
            delta.preserve.on = False

    def _compact_row(self, key: bytes) -> None:
        cells = self.store.get(self.table, key, FAMILY)
        if len(cells) <= 1:
            if cells:
                qual, val = cells[0].qualifier, cells[0].value
                if len(qual) == 2 and codec.needs_float_fix(qual[1], val):
                    fixed_val = codec.fix_float_value(qual[1], val)
                    fixed_qual = bytes([
                        qual[0],
                        codec.fix_qualifier_flags(qual[1], len(fixed_val))])
                    self.store.put(self.table, key, FAMILY, fixed_qual,
                                   fixed_val)
                    if fixed_qual != qual:
                        self.store.delete(self.table, key, FAMILY, [qual])
            return
        qual, val = codec.compact_cells(
            [(c.qualifier, c.value) for c in cells])
        existing = {c.qualifier: c.value for c in cells}
        if existing.get(qual) != val:
            self.store.put(self.table, key, FAMILY, qual, val)
            self.compactionq.written_cells += 1
        to_delete = [c.qualifier for c in cells if c.qualifier != qual]
        if to_delete:
            self.store.delete(self.table, key, FAMILY, to_delete)
            self.compactionq.deleted_cells += len(to_delete)

    def compact_cells(self, cells) -> tuple[bytes, bytes]:
        """In-memory merge used by the query path (no storage writes)."""
        return codec.compact_cells([(c.qualifier, c.value) for c in cells])

    # ------------------------------------------------------------------
    # Read path helpers
    # ------------------------------------------------------------------

    def read_row(self, key: bytes,
                 cells: list | None = None) -> codec.Columns:
        """Decode one row (possibly multi-cell) into sorted columnar arrays."""
        if cells is None:
            cells = self.store.get(self.table, key, FAMILY)
        base_ts = codec.key_base_time(key)
        kept = [c for c in cells
                if len(c.qualifier) % 2 == 0 and c.qualifier]
        if not kept:
            return codec.columns_concat([])
        ts, f, i, isf, _ = codec_np.decode_cells_flat(
            [c.qualifier for c in kept], [c.value for c in kept],
            np.full(len(kept), base_ts, np.int64))
        if len(kept) == 1:
            # compacted cells are sorted by construction
            return codec.Columns(ts, f, i, isf)
        d, f, i, isf = codec_np.sort_dedup(ts, f, i, isf)
        return codec.Columns(d, f, i, isf)

    def scan_rows(self, start_key: bytes, stop_key: bytes,
                  key_regexp: bytes | None = None,
                  ) -> Iterator[tuple[bytes, codec.Columns]]:
        """Ordered scan yielding (row_key, decoded columns)."""
        for cells in self.store.scan(self.table, start_key, stop_key,
                                     family=FAMILY, key_regexp=key_regexp):
            yield cells[0].key, self.read_row(cells[0].key, cells)

    def scan_columns(self, start_key: bytes, stop_key: bytes,
                     key_regexp: bytes | None = None,
                     batch_cells: int = 1 << 16,
                     series_hint=None,
                     ) -> Iterator[tuple[bytes, codec.Columns]]:
        """Batched scan decode: same rows as scan_rows, but cells decode
        in vectorized passes of ~``batch_cells`` cells
        (codec_np.decode_cells_flat) — the query read hot path, where
        per-row decode overhead would otherwise dominate wide scans.
        Yields per row at row-aligned batch boundaries, so peak memory
        holds one batch of raw bytes + its decoded arrays, not the whole
        range's (scan_rows-style streaming with the vectorized win)."""
        rows: list[tuple[bytes, int]] = []
        quals: list[bytes] = []
        vals: list[bytes] = []
        bases: list[int] = []

        def decode_batch():
            ts, f, i, isf, cop = codec_np.decode_cells_flat(
                quals, vals, np.asarray(bases, np.int64))
            starts = np.zeros(len(quals) + 1, np.int64)
            if len(quals):
                np.cumsum(np.bincount(cop, minlength=len(quals)),
                          out=starts[1:])
            out = []
            ci = 0
            for key, ncells in rows:
                a, b = int(starts[ci]), int(starts[ci + ncells])
                ci += ncells
                if ncells > 1:
                    d, ff, ii, mm = codec_np.sort_dedup(
                        ts[a:b], f[a:b], i[a:b], isf[a:b])
                    cols = codec.Columns(d, ff, ii, mm)
                else:
                    cols = codec.Columns(ts[a:b], f[a:b], i[a:b],
                                         isf[a:b])
                out.append((key, cols))
            rows.clear(), quals.clear(), vals.clear(), bases.clear()
            return out

        for key, items in self.store.scan_raw(
                self.table, start_key, stop_key,
                family=FAMILY, key_regexp=key_regexp,
                series_hint=series_hint):
            base = codec.key_base_time(key)
            kept = 0
            for q, v in items:
                if len(q) % 2 != 0 or not q:
                    continue  # foreign/annotation cells: skipped like
                    # read_row
                quals.append(q)
                vals.append(v)
                bases.append(base)
                kept += 1
            rows.append((key, kept))
            if len(quals) >= batch_cells:
                yield from decode_batch()
        if rows:
            yield from decode_batch()

    def scan_series(self, start_key: bytes, stop_key: bytes,
                    **scan_kw):
        """``scan_block`` as (series_keys, per-series Columns dict):
        every series the scan met, and views of the block for those
        with a point."""
        block = self.scan_block(start_key, stop_key, **scan_kw)
        return block.series_keys, block.per_series()

    def scan_block(self, start_key: bytes, stop_key: bytes,
                   key_regexp: bytes | None = None,
                   batch_cells: int = 1 << 18,
                   series_hint=None, counts: dict | None = None,
                   series_keys=None) -> codec.SeriesBlock:
        """Whole-range columnar scan regrouped BY SERIES in vectorized
        passes: one codec.SeriesBlock, the range's points as four flat
        columns in (series, timestamp) order with each series' row
        bounds, from one global sort + one vectorized dedup pass
        instead of per-row Columns objects and per-series
        re-concatenation. Profiled on the cold query path (the row-hour
        layout means ~10 points/row): per-row namedtuple construction +
        columns_concat of ~168 hour-parts per series cost more than the
        decode itself; here both collapse into a handful of
        whole-range numpy ops, and nothing downstream has to undo
        them: the block is what the query fragment cache keeps and
        what a fused request packs its point stream from. Duplicate
        (series, ts) points collapse when value-equal and raise
        IllegalDataError otherwise — sort_dedup's rule (reference
        complexCompact :600-679).
        ``counts``, when given, has the rows read added under "rows".
        ``series_hint`` / ``series_keys``: see KVStore.scan_raw."""
        from opentsdb_tpu.core.errors import IllegalDataError
        rows = 0
        quals: list[bytes] = []
        vals: list[bytes] = []
        bases: list[int] = []
        cell_sid: list[int] = []
        skey_index: dict[bytes, int] = {}
        skeys: list[bytes] = []
        parts: list[tuple] = []     # decoded (ts, f, i, isf, sid) batches

        def decode_batch():
            ts, f, i, isf, cop = codec_np.decode_cells_flat(
                quals, vals, np.asarray(bases, np.int64))
            sid = np.asarray(cell_sid, np.int64)[cop]
            parts.append((ts, f, i, isf, sid))
            quals.clear(), vals.clear(), bases.clear(), cell_sid.clear()

        for key, items in self.store.scan_raw(
                self.table, start_key, stop_key,
                family=FAMILY, key_regexp=key_regexp,
                series_hint=series_hint, series_keys=series_keys):
            rows += 1
            base = codec.key_base_time(key)
            skey = codec.series_key(key)
            si = skey_index.get(skey)
            if si is None:
                si = skey_index[skey] = len(skeys)
                skeys.append(skey)
            for q, v in items:
                if len(q) % 2 != 0 or not q:
                    continue
                quals.append(q)
                vals.append(v)
                bases.append(base)
                cell_sid.append(si)
            if len(quals) >= batch_cells:
                decode_batch()
        if quals:
            decode_batch()
        if counts is not None:
            counts["rows"] = counts.get("rows", 0) + rows
        if not parts:
            return codec.SeriesBlock(
                skeys, np.zeros(len(skeys) + 1, np.int64),
                codec.columns_concat([]))
        ts = np.concatenate([p[0] for p in parts])
        f = np.concatenate([p[1] for p in parts])
        i = np.concatenate([p[2] for p in parts])
        isf = np.concatenate([p[3] for p in parts])
        sid = np.concatenate([p[4] for p in parts])
        # Rows come in key order, so a series' cells already arrive
        # in time order and one stable sort by series regroups them;
        # where some series' do not (overlapping cells of several
        # tiers), the two-key sort gives the order. Either way the
        # result is sorted by (series, timestamp), ties in scan order.
        order = np.argsort(sid, kind="stable")
        if len(ts) > 1:
            st, ss = ts[order], sid[order]
            if ((ss[1:] == ss[:-1]) & (st[1:] < st[:-1])).any():
                order = np.lexsort((ts, sid))
        ts, f, i, isf, sid = (ts[order], f[order], i[order], isf[order],
                              sid[order])
        if len(ts) > 1:
            dup = (sid[1:] == sid[:-1]) & (ts[1:] == ts[:-1])
            if dup.any():
                same = ((isf[1:] == isf[:-1])
                        & np.where(isf[1:], f[1:] == f[:-1],
                                   i[1:] == i[:-1]))
                if (dup & ~same).any():
                    bad = int(ts[1:][dup & ~same][0])
                    raise IllegalDataError(
                        f"Found out of order or duplicate data: "
                        f"ts={bad} -- run an fsck.")
                keep = np.concatenate(([True], ~dup))
                ts, f, i, isf, sid = (ts[keep], f[keep], i[keep],
                                      isf[keep], sid[keep])
        bounds = np.searchsorted(sid, np.arange(len(skeys) + 1))
        return codec.SeriesBlock(skeys, bounds,
                                 codec.Columns(ts, f, i, isf))

    # ------------------------------------------------------------------
    # Suggest / admin / lifecycle
    # ------------------------------------------------------------------

    def suggest_metrics(self, prefix: str = "") -> list[str]:
        return self.metrics.suggest(prefix)

    def suggest_tag_names(self, prefix: str = "") -> list[str]:
        return self.tagk.suggest(prefix)

    def suggest_tag_values(self, prefix: str = "") -> list[str]:
        return self.tagv.suggest(prefix)

    def drop_caches(self) -> None:
        """Forget every cached name <-> UID mapping: the three UID
        caches and the resolved series built from them, so that a name
        renamed or repaired out of band (``uid rename``, fsck) resolves
        afresh at its next put."""
        self.metrics.drop_caches()
        self.tagk.drop_caches()
        self.tagv.drop_caches()
        self._series_cache.clear()

    def flush(self) -> None:
        """Flush compactions then the storage engine (reference :384-417)."""
        self.compactionq.flush(cutoff=int(time.time()) - MAX_TIMESPAN - 1)
        self.store.flush()

    def checkpoint(self) -> int:
        """Spill memtable state to the sstable tier and truncate the WAL
        (the TPU build's checkpoint/resume story, SURVEY §5.4). Returns
        rows spilled, 0 when the store is non-persistent.

        The sketch snapshot commits BEFORE the storage spill: the spill
        truncates the WAL, so committing after would mean a crash in
        between loses every fold since the previous snapshot (nothing
        left to replay). Committing first over-covers instead — a crash
        before the spill leaves a snapshot that already includes the
        still-replayable memtable, and recovery's re-fold double-counts
        it: exact for HLLs (register max is idempotent), within sketch
        tolerance for digests (the tradeoff the module doc accepts)."""
        if getattr(self.store, "read_only", False):
            # A replica owns neither the sketch snapshot nor the spill
            # tier; writing either would race the writer daemon.
            return 0
        if not hasattr(self, "rollups"):
            # The compaction thread's timer fired while __init__ is
            # still running (refilling the device window can outlast a
            # short checkpoint interval): ``rollups`` is the last thing
            # it sets, and there is nothing to checkpoint before that.
            return 0
        # One checkpoint at a time (see _checkpoint_lock): the rollup
        # bracketing below is only sound when THIS call's store spill is
        # the one between its begin_spill and fold_after_spill.
        with self._checkpoint_lock:
            # The two snapshots run before the store's timed phases
            # (checkpoint.phase); each is a checkpoint.snapshot timer
            # and a profiler annotation of that name (obs/trace.py).
            path = self._sketch_path()
            if self.sketches is not None and path:
                with obs_trace.timed("checkpoint.snapshot", kind="sketch"):
                    self.sketches.save(path)
            # Tenant accounting snapshot, same bracket position and
            # the same coverage argument: committed BEFORE the spill,
            # so a loaded TENANTS.json always covers the sstable tier
            # and boot only re-folds the replayed memtable's series.
            if self.tenants is not None:
                with obs_trace.timed("checkpoint.snapshot", kind="tenant"):
                    self.tenants.save()
            # Rollup tier brackets the spill: mark the about-to-spill
            # windows in flight (and the tier pending on disk) BEFORE the
            # raw spill, fold the spilled keys into summary records after —
            # a crash in between leaves the pending marker and the next
            # open rebuilds (rollup/tier.py consistency contract).
            rollups = self.rollups
            if rollups is not None:
                rollups.begin_spill()
            ckpt = getattr(self.store, "checkpoint", None)
            rows = ckpt() if ckpt else 0
            if rollups is not None:
                rollups.fold_after_spill()
            return rows

    def shutdown(self) -> None:
        # Idempotent: the CLI dispatcher sweeps any TSDB a command
        # opened (exception/early-return safety net), which may run
        # after the command already shut down cleanly itself.
        if getattr(self, "_shutdown_done", False):
            return
        self._shutdown_done = True
        try:
            self.compactionq.shutdown()
            if self.sketches is not None and self._sketch_path():
                # Spill + snapshot in one window: the snapshot's
                # coverage contract (== the sstable tier) must hold on
                # the next boot, where the replayed memtable is
                # re-folded on top of it.
                self.checkpoint()
            elif self.tenants is not None and self.tenants.path:
                # Tenant snapshot WITHOUT forcing a spill: accounting
                # folds are idempotent by series hash, so a snapshot
                # covering MORE than the sstable tier is harmless on
                # the next boot (the WAL replay's re-fold dedups) —
                # and it keeps exact per-tenant attribution for the
                # memtable-resident series instead of re-attributing
                # them to the default tenant at reopen.
                self.tenants.save()
            self.store.flush()
        finally:
            # Rollups close FIRST: their close() stops + joins the
            # catch-up thread, which READS the raw store — closing the
            # store before the thread stops would make the rebuild die
            # on closed fds with _stop unset and be misrecorded as a
            # catch-up FAILURE (spurious _rebuild_error) instead of an
            # orderly shutdown abort.
            try:
                if getattr(self, "rollups", None) is not None:
                    self.rollups.close()
            finally:
                # The store MUST close even when checkpoint/flush (or
                # the rollup close) raise — ENOSPC is a first-class
                # path: close releases the WAL's single-writer flock,
                # without which every later open of this path in the
                # process is refused.
                try:
                    close = getattr(self.store, "close", None)
                    if close:
                        close()
                finally:
                    dereg, self._deregister = self._deregister, None
                    if dereg:
                        dereg()

    def collect_stats(self, collector) -> None:
        """Push internal counters into a StatsCollector (reference :129-175)."""
        collector.record("datapoints.added", self.datapoints_added)
        for uid in (self.metrics, self.tagk, self.tagv):
            kind = uid.kind()
            collector.record("uid.cache-hit", uid.cache_hits, f"kind={kind}")
            collector.record("uid.cache-miss", uid.cache_misses,
                             f"kind={kind}")
            collector.record("uid.cache-size", uid.cache_size(),
                             f"kind={kind}")
        wal_errs = getattr(self.store, "wal_swallowed_flush_errors", None)
        if wal_errs is not None:
            collector.record("storage.wal.swallowed_flush_errors",
                             wal_errs)
        nshards = getattr(self.store, "shard_count", None)
        if nshards is not None:
            collector.record("storage.shards", nshards)
        rows_fn = getattr(self.store, "memtable_row_counts", None)
        if rows_fn is not None:
            # Live-memtable row count per shard: the skew view (one
            # hot shard = one slow spill join) the per-shard spill
            # timers explain after the fact; this shows it live.
            for i, n in enumerate(rows_fn(self.table)):
                collector.record("storage.memtable.rows", n,
                                 f"shard={i}")
        fmt_fn = getattr(self.store, "sstable_format_bytes", None)
        if fmt_fn is not None:
            for fmt, nbytes in sorted(fmt_fn().items()):
                collector.record("sstable.bytes", nbytes,
                                 f"format=v{fmt}")
        comp_fn = getattr(self.store, "compress_stats", None)
        if comp_fn is not None:
            raw, enc = comp_fn()
            if enc:
                # Uncompressed-record bytes per stored byte across the
                # v4 generations — `tsdb check --stats-metric
                # tsd.compress.ratio -x lt 1.5` alerts on a corpus
                # that stopped compressing.
                collector.record("compress.ratio",
                                 round(raw / enc, 4))
        bloom_files = getattr(self.store, "bloom_files_skipped", None)
        if bloom_files is not None:
            collector.record("bloom.files_skipped", bloom_files)
        bloom_shards = getattr(self.store, "bloom_shards_skipped", None)
        if bloom_shards is not None:
            collector.record("bloom.shards_skipped", bloom_shards)
        bloom_points = getattr(self.store, "bloom_point_skips", None)
        if bloom_points is not None:
            collector.record("bloom.point_skips", bloom_points)
        dirty = getattr(self.store, "dirty_bases", None)
        if dirty is not None:
            collector.record("dirty_set.size",
                             int(len(dirty(self.table))))
        if self.cluster_epoch_path:
            # Writers export the epoch they OWN; replicas (and a
            # fenced ex-writer) export the persisted file's view —
            # divergence between daemons is exactly the skew signal
            # the check tool alerts on.
            epoch = getattr(self.store, "writer_epoch", None)
            if epoch is None:
                from opentsdb_tpu.cluster.epoch import read_epoch
                try:
                    epoch, _ = read_epoch(self.cluster_epoch_path)
                except (OSError, ValueError, KeyError):
                    epoch = None
            if epoch is not None:
                collector.record("cluster.epoch", int(epoch))
            guard = getattr(self.store, "epoch_guard", None)
            if guard is not None:
                collector.record("cluster.fenced", int(guard.fenced))
            refused = getattr(self.store, "fenced_bytes_refused", 0)
            if refused:
                collector.record("cluster.fenced_bytes_refused",
                                 refused)
        cq = self.compactionq
        collector.record("compaction.count", cq.written_cells)
        collector.record("compaction.deleted_cells", cq.deleted_cells)
        collector.record("compaction.errors", cq.errors)
        collector.record("compaction.queue.size", len(cq))
        if self.sketches is not None:
            collector.record("sketches.series",
                             self.sketches.series_count())
        if self.tenants is not None:
            self.tenants.collect_stats(collector)
        if self.devwindow is not None:
            self.devwindow.collect_stats(collector)
        if self.devwindow_refill is not None:
            collector.record("devwindow.refill.ms",
                             self.devwindow_refill["ms"])
        if self.rollups is not None:
            self.rollups.collect_stats(collector)
