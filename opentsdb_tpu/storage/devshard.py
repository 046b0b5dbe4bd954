"""Mesh-sharded device-resident hot set — the serving fleet's window.

``DeviceWindow`` (devstore.py) keeps one process's recent ingest
resident on ONE device; its capacity is that chip's HBM and every
query's stage kernels run there. This module shards the same hot set
across the mesh on the series axis: K logical shards, each a
``DeviceWindow`` pinned to one mesh device (``device=`` in devstore),
series routed by the fleet-wide identity hash
(``storage.sstable.series_hash`` — the same hash the storage sharder,
the TSST3 blooms, and the serve router use). Capacity and dashboard
throughput then scale with mesh width instead of per-process host RAM:
each shard's stage kernel folds only its own series' chunks ON ITS OWN
DEVICE (committed inputs pin the jit execution), and only the tiny
[S_shard, B] grids travel to device 0 for the group combine.

Logical vs physical: ``n_shards`` may exceed the device count (shards
round-robin over the devices), so the tier-1 suite exercises the whole
sharded path — routing, per-shard eviction independence, reshard,
crash recovery — on a single CPU device.

Exactness: unchanged from devstore. A series lives in EXACTLY one
shard, each shard's window keeps the per-series exact-coverage
contract (monotone appends, complete_from, sticky dirty marks), so the
union serves a query iff every shard that owns any of the metric's
series can serve it; otherwise the whole window declines to the scan
path. Per-shard eviction is independent by construction — a shard
evicting its oldest chunk never touches a neighbor device's columns.

RESHARD (mesh grows/shrinks, ownership handoff) is live and follows
the coherent-swap discipline of ``ReadOnlyRollupTier.refresh``: build
the NEW shard set complete off to the side, swap whole under the lock.

1. gate: journaling on, every old shard quiesced (staged batches
   uploaded, in-flight uploads drained) — appends block only for this
   drain; from here ingest dual-writes (old set keeps serving exact
   answers, the journal feeds the new set);
2. rebuild: device columns fetched back per shard, split per series,
   redistributed by ``series_hash % n_new`` into freshly pinned
   windows (coverage floors carried: a series' new ``complete_from``
   is the max over its metric's old shards);
3. drain: journal replayed in passes until nearly empty, then a final
   gated pass, the ``mesh.reshard.commit`` faultpoint, and the
   atomic swap (generation bump invalidates every derived cache).

A query that snapshotted the old shard list mid-reshard finishes on
the old set — pre-swap answers are complete, never a mix of old and
new columns. A crash at the commit point loses only device state
(the hot set is a cache); reopen + warm rebuilds a coherent set from
storage, which the crash-matrix ``meshreshard`` scenario proves.
"""

from __future__ import annotations

import threading
import time as _time
from typing import NamedTuple

import numpy as np

from ..fault import faultpoints
from .devstore import DeviceWindow, record_device_memory
from .sstable import series_hash


class ShardedDevChunks(NamedTuple):
    """One metric's resident window across every shard, ready for the
    per-shard stage kernels. Row order of the combined result is shard
    order: combined sid = shard_starts[i] + local sid."""
    shards: list            # per-shard DevChunks | None (no series routed)
    shard_starts: list      # combined-sid offset of each shard's rows
    series_keys: list       # combined directory (concat in shard order)
    generation: tuple       # (reshard_gen, per-shard generations)
    version: tuple          # (reshard_gen, per-shard (instance, version))

    def narrowed(self, sids: np.ndarray, start: int,
                 end: int) -> "ShardedDevChunks":
        """DevChunks.narrowed, shard by shard: every shard's selection
        cut to those of the sorted combined ``sids`` that are its own
        rows; ``self`` where no shard's selection is narrowed by it."""
        cuts = np.searchsorted(
            sids, self.shard_starts + [len(self.series_keys)])
        shards = [None if sc is None
                  else sc.narrowed(sids[lo:hi] - first, start, end)
                  for sc, first, lo, hi in zip(
                      self.shards, self.shard_starts, cuts, cuts[1:])]
        if all(a is b for a, b in zip(shards, self.shards)):
            return self
        return self._replace(shards=shards)


class ShardedDeviceWindow:
    """Series-hash-sharded fleet of device-pinned ``DeviceWindow``s."""

    _instances = 0

    def __init__(self, devices=None, n_shards: int | None = None,
                 staging_points: int = 1 << 20,
                 max_points: int = 1 << 26,
                 background: bool = True,
                 stall_timeout: float = 60.0) -> None:
        if devices is None:
            devices = [None]
        devices = list(devices)
        if n_shards is None:
            n_shards = max(len(devices), 1)
        ShardedDeviceWindow._instances += 1
        self.instance_id = ("sharded", ShardedDeviceWindow._instances)
        self.staging_points = staging_points
        self.max_points = max_points
        self.background = background
        self.stall_timeout = stall_timeout
        self._lock = threading.RLock()
        self._devices = devices
        self._shards = self._build_shards(n_shards, devices)
        # Which shards have seen each metric: lets chunk_columns skip
        # shards with nothing routed to them (a DeviceWindow miss there
        # would otherwise veto the whole window).
        self._metric_shards: dict[bytes, set[int]] = {}
        # Sticky fleet-level dirty marks: survive reshard (a reshard
        # must never resurrect a window storage has diverged from).
        self._dirty_metrics: set[bytes] = set()
        # Dual-write journal, non-None only while a reshard is running.
        self._journal: list | None = None
        self.generation = 0          # bumps on every committed reshard
        # stats
        self.reshard_count = 0
        self.reshard_ms = 0.0        # last committed reshard, wall ms
        self.dirty_fallbacks = 0
        self.window_hits = 0
        self.window_misses = 0
        self.horizon_misses = 0
        self._why = threading.local()   # DeviceWindow.last_miss's twin

    def _build_shards(self, n_shards: int, devices) -> list[DeviceWindow]:
        per = max(self.max_points // max(n_shards, 1), 1)
        return [DeviceWindow(staging_points=self.staging_points,
                             max_points=per,
                             background=self.background,
                             stall_timeout=self.stall_timeout,
                             device=devices[i % len(devices)]
                             if devices else None)
                for i in range(n_shards)]

    @property
    def n_shards(self) -> int:
        return len(self._shards)

    def shard_of(self, series_key: bytes) -> int:
        return series_hash(series_key) % len(self._shards)

    # -- ingest side ---------------------------------------------------

    def append(self, metric_uid: bytes, series_key: bytes,
               timestamps: np.ndarray, values: np.ndarray) -> None:
        self.append_many(metric_uid, (series_key,), None, timestamps,
                         values)

    def append_many(self, metric_uid: bytes, series_keys,
                    series_of_point: np.ndarray | None,
                    timestamps: np.ndarray, values: np.ndarray) -> None:
        """``DeviceWindow.append_many`` over the fleet: each series'
        points go to the shard its key hashes to, one call a shard."""
        if len(timestamps) == 0:
            return
        with self._lock:
            routed = self._route(metric_uid, series_keys)
            if routed is None:
                return
            owner, owners = routed
            if self._journal is not None:
                self._journal_rows(
                    metric_uid, series_keys,
                    [len(timestamps)] if series_of_point is None
                    else np.bincount(series_of_point,
                                     minlength=len(series_keys)),
                    timestamps, values)
            # Delegate under the fleet lock: the reshard gate's
            # quiesce+snapshot must never interleave with a half-landed
            # append (staged in neither the snapshot nor the journal).
            if series_of_point is None or len(owners) == 1:
                self._shards[owners[0]].append_many(
                    metric_uid, series_keys, series_of_point,
                    timestamps, values)
                return
            owner_of_point = owner[series_of_point]
            local = np.zeros(len(series_keys), np.int64)
            for idx in owners:
                mine = np.flatnonzero(owner == idx)
                local[mine] = np.arange(len(mine))
                pts = np.flatnonzero(owner_of_point == idx)
                self._shards[idx].append_many(
                    metric_uid, [series_keys[j] for j in mine],
                    local[series_of_point[pts]], timestamps[pts],
                    values[pts])

    def append_rows(self, metric_uid: bytes, series_keys, counts,
                    timestamps: np.ndarray, values: np.ndarray) -> None:
        """``DeviceWindow.append_rows`` over the fleet (the boot's
        refill from columnar blocks): the run's rows go, in the run's
        order, to the shards their series hash to, one call a shard,
        and each shard cuts its chunks as a row at a time would."""
        if len(timestamps) == 0:
            return
        counts = np.asarray(counts)
        with self._lock:
            routed = self._route(metric_uid, series_keys)
            if routed is None:
                return
            owner, owners = routed
            if self._journal is not None:
                self._journal_rows(metric_uid, series_keys, counts,
                                   timestamps, values)
            owner_of_point = np.repeat(owner, counts)
            for idx in owners:
                mine = np.flatnonzero(owner == idx)
                pts = owner_of_point == idx
                self._shards[idx].append_rows(
                    metric_uid, [series_keys[j] for j in mine],
                    counts[mine], timestamps[pts], values[pts])

    def _route(self, metric_uid: bytes, series_keys):
        """(the shard of each of ``series_keys``, the shards among
        them), noted as owners of the metric; None for a dirty metric.
        The caller holds the fleet lock."""
        if metric_uid in self._dirty_metrics:
            return None
        n_shards = len(self._shards)
        owner = np.fromiter(
            (series_hash(k) % n_shards for k in series_keys),
            np.int64, len(series_keys))
        owners = np.unique(owner).tolist()
        self._metric_shards.setdefault(metric_uid, set()).update(owners)
        return owner, owners

    def _journal_rows(self, metric_uid: bytes, series_keys, counts,
                      timestamps, values) -> None:
        """Journal COPIES under the gate lock: the record must be
        immutable (replay happens later) and ordered with the reshard's
        snapshot boundary. A record a series (series ``i`` holds the
        next ``counts[i]`` points): a reshard is rare, and its replay
        re-routes by series."""
        ts = np.array(timestamps, np.int64)
        vals = np.array(values, np.float32)
        ends = np.cumsum(counts)
        for key, a, z in zip(series_keys, ends - counts, ends):
            if z > a:
                self._journal.append(
                    (metric_uid, key, ts[a:z], vals[a:z]))

    def flush(self) -> None:
        with self._lock:
            shards = list(self._shards)
        for s in shards:
            s.flush()

    def invalidate(self, metric_uid: bytes | None = None) -> None:
        with self._lock:
            if metric_uid is None:
                self._dirty_metrics.update(self._metric_shards)
            else:
                self._dirty_metrics.add(metric_uid)
            shards = list(self._shards)
        for s in shards:
            s.invalidate(metric_uid)

    # -- query side ----------------------------------------------------

    def chunk_columns(self, metric_uid: bytes, start: int,
                      end: int) -> ShardedDevChunks | None:
        """The metric's resident columns across every owning shard when
        ALL of them exactly cover [start, end]; None = scan fallback.
        Snapshot-consistent under reshard: the shard list is captured
        once, so a concurrent swap leaves this query on the complete
        pre-swap set, never a mix."""
        self._why.reason = None
        with self._lock:
            if metric_uid in self._dirty_metrics:
                self.dirty_fallbacks += 1
                self._why.reason = "dirty"
                return None
            shards = list(self._shards)
            gen = self.generation
            owners = sorted(self._metric_shards.get(metric_uid, ()))
        if not owners:
            self.window_misses += 1
            self._why.reason = "absent"
            return None
        per = [None] * len(shards)
        for i in owners:
            if i >= len(shards):     # mapping raced a shrink; decline
                self.window_misses += 1
                self._why.reason = "absent"
                return None
            cols = shards[i].chunk_columns(metric_uid, start, end)
            if cols is None:
                # An owning shard declined (dirty / evicted coverage /
                # slow upload): a partial union would be WRONG, so the
                # whole window falls back to the scan path.
                self.window_misses += 1
                why = self._why.reason = shards[i].last_miss()
                self.horizon_misses += why == "horizon"
                return None
            per[i] = cols
        starts, keys = [], []
        for cols in per:
            starts.append(len(keys))
            if cols is not None:
                keys.extend(cols.series_keys)
        self.window_hits += 1
        return ShardedDevChunks(
            shards=per,
            shard_starts=starts,
            series_keys=keys,
            generation=(gen, tuple(
                c.generation if c is not None else -1 for c in per)),
            version=(gen, tuple(
                (shards[i].instance_id, per[i].version)
                if per[i] is not None else (0, -1)
                for i in range(len(shards)))))

    # -- reshard -------------------------------------------------------

    def reshard(self, n_shards: int | None = None,
                devices=None) -> dict:
        """Live redistribution of the hot set over a new shard count /
        device list. Returns a stats dict. Serialized: concurrent calls
        run back to back."""
        t0 = _time.monotonic()
        if devices is None:
            devices = self._devices
        devices = list(devices) if devices else [None]
        if n_shards is None:
            n_shards = max(len(devices), 1)
        # Phase 1 — gate: journaling on + old set fully materialized
        # into device chunks (appends block only for this drain).
        with self._lock:
            if self._journal is not None:
                raise RuntimeError("reshard already in progress")
            self._journal = []
            old = list(self._shards)
            for s in old:
                s.quiesce()
            snaps = [s._snapshot_metrics() for s in old]
            dirty = set(self._dirty_metrics)
        # Phase 2 — rebuild off-gate (old set serves, journal fills).
        new = self._build_shards(n_shards, devices)
        new_owner: dict[bytes, set[int]] = {}
        try:
            for uid in sorted({u for sn in snaps for u in sn}):
                if uid in dirty or any(
                        sn.get(uid, {}).get("dirty") for sn in snaps):
                    dirty.add(uid)
                    continue
                floor = None
                for sn in snaps:
                    cf = sn.get(uid, {}).get("complete_from")
                    if cf is not None:
                        floor = cf if floor is None else max(floor, cf)
                per_series = self._split_series(
                    [sn[uid] for sn in snaps if uid in sn])
                for key, (ts, vals) in per_series.items():
                    j = series_hash(key) % n_shards
                    new[j].append(uid, key, ts, vals)
                    new_owner.setdefault(uid, set()).add(j)
                if floor is not None:
                    for j in new_owner.get(uid, ()):
                        new[j].set_complete_from(uid, floor)
            # Phase 3 — drain the journal in passes, then the gated
            # commit. Each pass replays what accumulated while the
            # previous one ran; the final (small) remainder replays
            # under the lock so the swap sees a complete new set.
            while True:
                with self._lock:
                    batch, self._journal = self._journal, []
                if not batch:
                    break
                self._replay(batch, new, n_shards, new_owner, dirty)
                if len(batch) < 64:
                    break
            with self._lock:
                self._replay(self._journal, new, n_shards, new_owner,
                             dirty)
                self._journal = None
                # Crash here = SIGKILL at the commit: the swap never
                # happens, the old set keeps serving (stale-but-
                # complete), and a restart rebuilds from storage.
                faultpoints.fire("mesh.reshard.commit")
                self._shards = new
                self._devices = devices
                self._metric_shards = new_owner
                self._dirty_metrics = dirty
                self.generation += 1
                self.reshard_count += 1
                self.reshard_ms = (_time.monotonic() - t0) * 1e3
                return {"n_shards": n_shards,
                        "generation": self.generation,
                        "metrics": len(new_owner),
                        "dirty_metrics": len(dirty),
                        "reshard_ms": round(self.reshard_ms, 2)}
        except BaseException:
            with self._lock:
                self._journal = None     # abort: old set stays live
            raise

    @staticmethod
    def _split_series(metric_snaps: list[dict]) -> dict:
        """Per-series (abs_ts, vals) in append order from the refs-only
        snapshots of one metric across its old shards. A series lives
        in exactly one shard, and within a shard its points are in time
        order across seq-ordered chunks, so per-key concatenation
        preserves the strict-monotone append contract."""
        out: dict[bytes, list] = {}
        for sn in metric_snaps:
            keys = sn["keys"]
            epoch = sn["epoch"]
            segs: dict[int, list] = {}
            for ch in sn["chunks"]:
                v = np.asarray(ch["valid"])
                sid = np.asarray(ch["sid"])[v]
                ts = np.asarray(ch["ts"])[v].astype(np.int64) + epoch
                vals = np.asarray(ch["vals"])[v]
                order = np.argsort(sid, kind="stable")
                sid_o, ts_o, vals_o = sid[order], ts[order], vals[order]
                bounds = np.searchsorted(
                    sid_o, np.arange(len(keys) + 1))
                for s in range(len(keys)):
                    lo, hi = bounds[s], bounds[s + 1]
                    if hi > lo:
                        segs.setdefault(s, []).append(
                            (ts_o[lo:hi], vals_o[lo:hi]))
            for s, parts in segs.items():
                ts_cat = np.concatenate([p[0] for p in parts])
                vl_cat = np.concatenate([p[1] for p in parts])
                out[keys[s]] = (ts_cat, vl_cat)
        return out

    @staticmethod
    def _replay(batch, new, n_shards, new_owner, dirty) -> None:
        for uid, key, ts, vals in batch:
            if uid in dirty:
                continue
            j = series_hash(key) % n_shards
            new[j].append(uid, key, ts, vals)
            new_owner.setdefault(uid, set()).add(j)

    # -- observability -------------------------------------------------

    def shard_appended_points(self) -> list[int]:
        """Points each shard has taken in since it was built."""
        with self._lock:
            return [s.appended_points for s in self._shards]

    def shard_resident_points(self) -> list[int]:
        with self._lock:
            shards = list(self._shards)
        out = []
        for s in shards:
            with s._lock:
                out.append(sum(mw.device_points
                               for mw in s._metrics.values()))
        return out

    def resident_points(self) -> int:
        return sum(self.shard_resident_points())

    def shard_device_ids(self) -> list:
        """The jax device id each shard is pinned to (None = default
        placement)."""
        with self._lock:
            return [None if s.device is None else int(s.device.id)
                    for s in self._shards]

    def last_miss(self) -> str | None:
        """Why this thread's last chunk_columns() declined (the owning
        shard's reason: see DeviceWindow.last_miss)."""
        return getattr(self._why, "reason", None)

    def collect_stats(self, collector) -> None:
        with self._lock:
            shards = list(self._shards)
        # Point/eviction/stall counters sum across shards; hit/miss/
        # dirty counters are FLEET-level (one query = one verdict, not
        # one per owning shard).
        agg = {"devwindow.points.appended": 0,
               "devwindow.points.evicted": 0,
               "devwindow.upload_stalls": 0,
               "devwindow.metrics": 0,
               "devwindow.points.resident": 0,
               "devwindow.chunks": 0}

        class _Sink:
            def record(self, name, value):
                if name in agg:
                    agg[name] += value
        sink = _Sink()
        # A device runs out alone: devwindow.bytes is what the shards
        # of the fullest device hold, and device.* are that device's.
        held: dict = {}
        for s in shards:
            s.collect_stats(sink, device=False)
            held[s.device] = held.get(s.device, 0) + s._total_bytes
        for name, value in agg.items():
            collector.record(name, value)
        fullest = max(held, key=held.get)
        collector.record("devwindow.bytes", held[fullest])
        record_device_memory(collector, fullest)
        collector.record("devwindow.hits", self.window_hits)
        collector.record("devwindow.misses", self.window_misses)
        collector.record("devwindow.misses.horizon", self.horizon_misses)
        collector.record("devwindow.dirty_fallbacks",
                         self.dirty_fallbacks)
        collector.record("devwindow.points.budget", self.max_points)
        # A shard evicts alone, so the fleet's horizons are the extremes
        # over the shards that hold data.
        spans = [h for h in (s.horizons() for s in shards) if h]
        collector.record("devwindow.horizon.min",
                         min((h[0] for h in spans), default=0))
        collector.record("devwindow.horizon.max",
                         max((h[1] for h in spans), default=0))
        collector.record("mesh.resident.points",
                         agg["devwindow.points.resident"])
        collector.record("mesh.resident.shards", len(shards))
        # The window's bytes over all the devices, beside
        # devwindow.bytes, the fullest device's: their ratio is how
        # evenly the hash spread the series (a quarter of it over four).
        collector.record("mesh.resident.bytes", sum(held.values()))
        collector.record("mesh.resident.reshard.count",
                         self.reshard_count)
        collector.record("mesh.resident.reshard_ms",
                         round(self.reshard_ms, 2))
