"""Query executor: scan -> span assembly -> group-by -> batched compute.

Parity target: reference src/core/TsdbQuery.java + SpanGroup. The planner
reproduces the reference's query surface — exact-tag filtering pushed down
as a row-key regexp (:433-492), group-by materialization per distinct
combination of group-by tag values (:294-363), intersection/aggregated-tags
computation (SpanGroup.computeTags :149-173) — but executes each group as
one batched kernel call instead of a k-way merge of pull iterators.

Pipeline order matches the reference: per-span downsample first, then rate,
then cross-span aggregation (SGIterator composes downsampling iterators
:442-446 and computes rates from consecutive downsampled points :736-784),
with linear interpolation for plain aggregation and last-value-hold for
rates.

Backends: 'tpu' runs the jitted kernels from ops/ (padded shapes); 'cpu'
runs the float64 numpy oracle. Both backends agree bit-for-bit on grids
and to float32 tolerance on values.

Deliberate departure from 1.1 semantics (shared with OpenTSDB 2.x):
downsampled queries emit epoch-aligned bucket-start timestamps, so every
series shares one bucket grid and the group stage needs no per-pair
interpolation grids. The 1.1 behavior (data-driven windows, averaged
member timestamps, disjoint per-series grids) survives in
ops/oracle.downsample(mode='legacy', bucket_ts='avg') for parity testing.
Un-downsampled queries keep the exact 1.1 union-grid semantics.
"""

from __future__ import annotations

import collections
import concurrent.futures
import itertools
import re
import threading
import weakref
from typing import NamedTuple

import jax
import numpy as np

from opentsdb_tpu.core import codec
from opentsdb_tpu.core.const import (MAX_TIMESPAN, NOLERP_AGGS,
                                     TIMESTAMP_BYTES, UID_WIDTH)
from opentsdb_tpu.core.errors import BadRequestError
from opentsdb_tpu.fault.faultpoints import fire as _fault
from opentsdb_tpu.compress.devcache import pad_fine as _pad_fine
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.ops import kernels, oracle, sketches
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.storage.sstable import series_hash
from opentsdb_tpu.utils.lru import LRUCache

# Fused decode-plus-aggregate serving off TSST4 blocks (compress/):
# wall time of the gather + kernel dispatch per served query.
_M_FUSED = _metrics.timer("compress.fused_agg")

# Fused coverage accounting: attempts = queries past the fused gates
# (the fused-eligible battery), served = answered plan:"fused"; the
# gauge is their ratio, what /stats and /metrics expose. Every decline
# between the two increments compress.fused.decline{reason=} — the
# no-silent-declines contract is these three instruments agreeing.
_C_FUSED_ATTEMPT = _metrics.counter("compress.fused.attempt")
_C_FUSED_SERVED = _metrics.counter("compress.fused.served")
# What the gathers of the fused plan met: the points of the blocks they
# touched, of those the points of matching in-range records, the
# blocks' payload bytes (the larger stream of each), and the bytes the
# byte-stream leg sent to the device (the block cache counts
# its own fills: compress.devcache.uploaded_bytes).
_C_FUSED_POINTS = _metrics.counter("compress.fused.points")
_C_FUSED_MATCHED = _metrics.counter("compress.fused.matched_points")
_C_FUSED_PAYLOAD = _metrics.counter("compress.fused.payload_bytes")
_C_FUSED_UPLOADED = _metrics.counter("compress.fused.uploaded_bytes")
_metrics.gauge(
    "compress.fused.coverage",
    lambda: (_C_FUSED_SERVED.value / _C_FUSED_ATTEMPT.value
             if _C_FUSED_ATTEMPT.value else 0.0))

# The resident plan's stage cache (_dw_stage_cache): a miss builds a
# stage, which is the device's whole cost of a resident sub-query;
# evicted = stages dropped by hand (a dead data version, a device OOM),
# not the LRU's own turnover at its cap.
_C_STAGE_HIT = _metrics.counter("devwindow.stage.hit")
_C_STAGE_MISS = _metrics.counter("devwindow.stage.miss")
_C_STAGE_EVICTED = _metrics.counter("devwindow.stage.evicted")
# What the stages built were handed, in slots of the resident chunks
# (padding included): visited = the blocks the zone maps let through to
# window.chunk_fold, skipped = the rest. Together they are the slots
# resident a stage built.
_C_FOLD_VISITED = _metrics.counter("devwindow.fold.slots.visited")
_C_FOLD_SKIPPED = _metrics.counter("devwindow.fold.slots.skipped")
# Stages built with and without a cut by the matched series (their sum
# is devwindow.stage.miss): how often the series dimension of the zone
# maps engages.
_C_FOLD_NARROWED = _metrics.counter("devwindow.fold.stages.narrowed")
_C_FOLD_WHOLE = _metrics.counter("devwindow.fold.stages.whole")
# window.chunk_fold calls: a stage built issues one for every group of
# up to kernels._FOLD_GROUP chunks of one shape class its selection
# picked a block of (kernels.fold_groups), so dispatches /
# devwindow.stage.miss is the fold calls a stage, which grow with the
# span of the range, a group at a time, where the slots visited need
# not. stage.programs is every device program a stage build issued
# from Python: the start (the accumulators), each fold call and the
# finish, so 2 + the calls (a shard of the sharded window: its own
# start and finish), and each one a place where the stage's thread
# lets the interpreter lock go and has to win it back.
_C_FOLD_DISPATCHES = _metrics.counter("devwindow.fold.dispatches")
_C_STAGE_PROGRAMS = _metrics.counter("devwindow.stage.programs")
# The sharded window's stages (storage/devshard.py): the shards a stage
# built was folded on (a window_series_stage_chunks call each, so
# shards / stage.miss is the fan-out: every shard of the metric where
# no shard is dropped), and the bytes of the shards' grids that went
# from their device to the combine device.
_C_STAGE_SHARDS = _metrics.counter("devwindow.stage.shards")
_C_GATHER_BYTES = _metrics.counter("mesh.resident.gather.bytes")
# The updates the folds' scatters were handed (kernels._scatter_runs:
# one a run of equal (series, bucket) and not one a slot), beside
# devwindow.fold.slots.visited: their ratio is what the run reduction
# left of the scatters' work. A stage's count is a device scalar its
# folds carried; it waits in a _Handed until the stats are read (or
# _HANDED_MAX have gathered), so no sub-query pays a transfer for
# it. A gauge over a running total, and not a counter, for that reason.
_HANDED_MAX = 512


class _Handed:
    """The running total of the counts that stages left on the device."""

    def __init__(self):
        self._waiting: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._total = 0

    def total(self) -> int:
        """The updates handed over since boot: the stages' counts that
        were still on the device fetched and added to the total."""
        with self._lock:
            # One drainer at a time, and the others only append.
            handed = [self._waiting.popleft()
                      for _ in range(len(self._waiting))]
            if handed:
                self._total += sum(map(int, jax.device_get(handed)))
            return self._total

    def add(self, handed) -> None:
        """Keep one stage's count for the next reading of the stats."""
        self._waiting.append(handed)
        if len(self._waiting) > _HANDED_MAX:
            self.total()


_FOLD_HANDED = _Handed()
_metrics.gauge("devwindow.fold.updates", _FOLD_HANDED.total)
# The same of the plans that read past the horizon: the slots of the
# streams their stages were given (the raw plan's packed stream, the
# fused plan's whole blocks or matched points, padding and all) and the
# updates kernels._series_stage's scatters were handed for them.
_C_STAGE_SLOTS = _metrics.counter("query.stage.slots")
_STAGE_HANDED = _Handed()
_metrics.gauge("query.stage.updates", _STAGE_HANDED.total)


def _stage_handed(handed, slots: int) -> None:
    """Count one kernels._series_stage: ``handed`` its device scalar,
    ``slots`` the length of the stream it was given."""
    _C_STAGE_SLOTS.inc(slots)
    _STAGE_HANDED.add(handed)


# What the raw plan read from storage and handed to its kernels: rows
# decoded by the scans of raw sub-queries (a fragment-cache hit decodes
# none) and the points in range that went on to the aggregate stage.
_C_RAW_ROWS = _metrics.counter("query.raw.rows")
_C_RAW_POINTS = _metrics.counter("query.raw.points")
# Points packed into a stream for the fused downsample kernels, by
# where they were read from: the flat blocks of a raw scan (cached
# fragments and fresh chunk scans alike), or a list of spans (the
# rollup planner's per-bucket records, an expert batch's groups).
_C_PACK_FLAT = _metrics.counter("query.pack.flat_points")
_C_PACK_SPANS = _metrics.counter("query.pack.span_points")
# The groups a grid plan (resident, fused) answered, by where their
# labels came from: kept = taken from the plan that made the groups
# (_GridGroups.labels), computed = worked out on the request (the
# first answer of a plan builds its labels, and a group with a member
# that has no point in range is always labelled anew, over its live
# members). kept / (kept + computed) says how often the plans are
# still held when their groups are asked for again.
_C_LABELS_KEPT = _metrics.counter("query.results.labels.kept")
_C_LABELS_COMPUTED = _metrics.counter("query.results.labels.computed")


def _count_decline(reason: str) -> None:
    _metrics.counter("compress.fused.decline", {"reason": reason}).inc()


# One fragment cache PER STORE, shared by every QueryExecutor over it
# (the ROADMAP cross-executor follow-on): CLI one-shot executors, the
# server's executor, and test harnesses all warm the same LRU, so a
# second executor over the same store starts hot instead of re-decoding
# the working set. Keyed by store IDENTITY via a weak map — a closed
# store's cache dies with it, and id() reuse can't alias two stores.
# Fragment keys carry the table name, so two TSDBs sharing one store
# under different tables can't cross-serve fragments.
_FRAG_CACHES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_FRAG_CACHES_LOCK = threading.Lock()


def _shared_frag_cache(store, max_entries: int,
                       max_points: int) -> LRUCache:
    with _FRAG_CACHES_LOCK:
        cache = _FRAG_CACHES.get(store)
        if cache is None:
            cache = LRUCache(max_entries, max_cost=max_points)
            _FRAG_CACHES[store] = cache
        elif (cache.max_entries != max_entries
              or cache.max_cost != max_points):
            # A later executor with different bounds REBOUNDS the
            # shared instance in place (newest config wins) rather
            # than replacing it: existing executors hold direct
            # references, and swapping the map entry would strand them
            # on an orphaned cache — two full-size caches per store
            # and no cross-executor sharing, exactly what this
            # registry exists to prevent.
            cache.resize(max_entries, max_cost=max_points)
        return cache


class QuerySpec(NamedTuple):
    metric: str
    tags: dict[str, str]            # value '*' or 'v1|v2' => group by
    aggregator: str = "sum"
    rate: bool = False
    downsample: tuple[int, str] | None = None
    counter: bool = False           # rate rollover correction
    counter_max: float = float(2**64)
    reset_value: float | None = None


class QueryResult(NamedTuple):
    metric: str
    tags: dict[str, str]
    aggregated_tags: list[str]
    timestamps: np.ndarray          # int64 epoch seconds
    values: np.ndarray              # float64


class _Span(NamedTuple):
    series_key: bytes
    tags: dict[str, str]
    timestamps: np.ndarray
    values: np.ndarray


class _Scan:
    """What a sub-query's scan found, before a point of it is copied:
    the flat blocks (codec.SeriesBlock: a fragment of the range each,
    in time order), the rows of each block's series that lie in the
    range, and the series that have one, numbered in order of first
    appearance, named, and filed under their groups.

    The fused downsample kernels take it as one point ``stream``,
    written once and straight from the blocks; whatever works a series
    at a time (the float64 oracle, union-grid interpolation, the mesh
    packers) asks for ``spans``, views of the blocks as one."""

    def __init__(self, blocks: "list[codec.SeriesBlock]",
                 start: int | None = None,
                 end: int | None = None) -> None:
        self.blocks = blocks
        # No range: every row of the blocks (``of_spans``).
        self.range = (start, end)
        self.cuts = [self._cut(blk) for blk in blocks]
        index: dict[bytes, int] = {}
        # Per block, its series -> the request's (-1: no row in range).
        self.sids = []
        for blk, (lo, hi) in zip(blocks, self.cuts):
            live = np.flatnonzero(hi > lo)
            sid = np.full(len(blk.series_keys), -1, np.int32)
            sid[live] = [index.setdefault(blk.series_keys[i], len(index))
                         for i in live.tolist()]
            self.sids.append(sid)
        self.keys = list(index)
        self.points = sum(int((hi - lo).sum()) for lo, hi in self.cuts)
        self.tags: list[dict[str, str]] = []    # by request series
        self.groups: dict[tuple, list[int]] = {}

    def _cut(self, blk: "codec.SeriesBlock"):
        if self.range[0] is None:
            return blk.bounds[:-1], blk.bounds[1:]
        return blk.cut(*self.range)

    @classmethod
    def of_spans(cls, groups: "dict[tuple, list[_Span]]") -> "_Scan":
        """Span lists (every row of each in range) as a scan of one
        block: one concatenation a column."""
        spans = [sp for members in groups.values() for sp in members]
        bounds = np.zeros(len(spans) + 1, np.int64)
        np.cumsum([len(sp.timestamps) for sp in spans], out=bounds[1:])
        scan = cls([codec.SeriesBlock(
            [sp.series_key for sp in spans], bounds, codec.Columns(
                np.concatenate([sp.timestamps for sp in spans]),
                np.concatenate([sp.values for sp in spans]), None, None)
        )] if spans else [])
        scan.tags = [sp.tags for sp in spans]
        at = 0
        for gkey, members in groups.items():
            scan.groups[gkey] = list(range(at, at + len(members)))
            at += len(members)
        return scan

    def spans(self) -> "dict[tuple, list[_Span]]":
        """The groups as lists of per-series views of one block: the
        blocks merged first where the range took several."""
        if not self.blocks:
            return {}
        blk = codec.SeriesBlock.merged(self.blocks)
        lo, hi = self.cuts[0] if len(self.blocks) == 1 else self._cut(blk)
        row = dict(zip(blk.series_keys, zip(lo.tolist(), hi.tolist())))
        ts, vals = blk.cols.timestamps, blk.cols.values
        out = {}
        for gkey, members in self.groups.items():
            out[gkey] = group = []
            for i in members:
                a, b = row[self.keys[i]]
                group.append(_Span(self.keys[i], self.tags[i],
                                   ts[a:b], vals[a:b]))
        return out

    def stream(self, qbase: int, pad: bool = False):
        """The fused kernels' flat (rel_ts, vals, sid, valid) point
        stream, each array allocated once and filled block by block
        with whole-array operations: a fleet-wide request moves its
        points once, and the stream is all the large memory it touches
        for the first time (a fresh page costs more than the copy into
        it; and every numpy call may hand the GIL to another busy
        thread for a whole switch interval, where a wide request has
        thousands of series). Of a block, the rows in range are read as
        they lie where they are one run (a block wholly in range, one
        series) or a grid (series sampled in step, all cut alike), and
        gathered by one index vector otherwise.

        The stream is block-major: a series' points ascend in time
        (blocks are in time order, each in (series, time) order) but
        lie in as many runs as blocks hold it. The kernels reduce by
        (sid, bucket) and read no order.

        ``pad`` appends invalid slots up to the quarter-octave ladder
        (``pad_fine``), for the single-device kernels: the stream's
        length is a static shape of theirs, and a window that starts
        one second later holds one point a series more or less, which
        unpadded is a new program for every such length (a 12 h window
        of 10 s data: 4,320 or 4,321 points a series)."""
        n = self.points
        size = _pad_fine(n) if pad else n
        rel = np.empty(size, np.int32)
        vals = np.empty(size, np.float32)
        sid = np.empty(size, np.int32)
        scratch = np.empty((2, 0), np.int64)    # the gathers', grown once
        at = 0
        for blk, (lo, hi), series in zip(self.blocks, self.cuts,
                                         self.sids):
            live = np.flatnonzero(hi > lo)
            if not len(live):
                continue
            lo, count, series = lo[live], (hi - lo)[live], series[live]
            first = np.cumsum(count) - count
            m = int(first[-1] + count[-1])
            sid[at:at + m] = np.repeat(series, count)
            ts, v = blk.cols.timestamps, blk.cols.values
            a, c, shape, rows = int(lo[0]), int(count[0]), (m,), None
            if int(lo[-1] + count[-1]) - a == m:
                ts, v = ts[a:a + m], v[a:a + m]
            elif (count == c).all() and not np.diff(lo, 2).any():
                # As many rows of every series, as far apart (the cut of
                # series sampled in step): a 2-D view, read in place.
                shape = (len(lo), c)
                ts, v = (np.lib.stride_tricks.as_strided(
                    col[a:], shape,
                    (int(lo[1] - a) * col.itemsize, col.itemsize),
                    writeable=False) for col in (ts, v))
            else:
                if scratch.shape[1] < m:
                    scratch = np.empty((2, m), np.int64)
                # The rows of run k are lo[k], lo[k] + 1, ...: a step
                # of one, each run's jump from the last at its first
                # slot, summed in place (no temporary of m, as a repeat
                # and an arange each would make).
                rows = scratch[0, :m]
                rows[:] = 1
                rows[first] = lo - np.concatenate(
                    ([0], lo[:-1] + count[:-1] - 1))
                np.cumsum(rows, out=rows)
                # (clip: an `out` is buffered otherwise; every row is
                # in bounds.)
                ts = np.take(ts, rows, out=scratch[1, :m], mode="clip")
            np.subtract(ts, qbase, out=rel[at:at + m].reshape(shape),
                        casting="unsafe")
            if rows is not None:
                v = np.take(v, rows, out=ts.view(np.float64), mode="clip")
            vals[at:at + m].reshape(shape)[...] = v
            at += m
        for column in (rel, vals, sid):
            column[n:] = 0
        valid = np.zeros(size, bool)
        valid[:n] = True
        (_C_PACK_SPANS if self.range[0] is None else _C_PACK_FLAT).inc(n)
        return rel, vals, sid, valid


class QueryExecutor:
    def __init__(self, tsdb, backend: str | None = None,
                 mesh=None) -> None:
        """``mesh``: optional jax.sharding.Mesh. When set, fused
        downsample queries distribute over it — series-sharded
        (parallel.sharded) when a group has at least one series per
        chip, time-sharded (parallel.timeshard) for long single-series
        ranges — with psum/all-gather fan-in. Without a mesh every
        kernel runs single-device (the reference's whole deployment
        model is single-process per TSD; the mesh is this build's
        scale-up axis)."""
        self.tsdb = tsdb
        self.backend = backend or tsdb.config.backend
        if mesh is not None:
            # The query kernels shard over the series-hash axis; any
            # (host, series) factorization flattens here — the hybrid
            # structure matters to the DCN-aware multihost kernels,
            # not to dashboard reductions.
            from opentsdb_tpu.parallel.plan import flatten_series_mesh
            mesh = flatten_series_mesh(mesh)
        self.mesh = mesh
        # Scan-phase latency digest, the analog of TsdbQuery.scanlatency
        # (reference src/core/TsdbQuery.java:52,278).
        from opentsdb_tpu.stats.collector import LatencyDigest
        self.scan_latency = LatencyDigest()
        # Planner choice of the most recent run(): "raw", "resident"
        # (device window), or a rollup resolution label ("1h"/"1d").
        # A single-threaded convenience mirror (tests, benches); the
        # server reads the label run_with_plan() RETURNS instead —
        # concurrent requests sharing one executor would otherwise
        # report a neighbor query's label in JSON metadata.
        self.last_plan = "raw"
        cfg = tsdb.config
        # Fragment cache (the query fast path): decoded per-(selector,
        # aligned time-chunk) flat blocks (codec.SeriesBlock), validated
        # against the store's content epochs + dirty-base set
        # (_scan_blocks).
        # Bounded by cached POINTS, not entries — fragments range from
        # bytes to megabytes. ONE cache per store process-wide (see
        # _shared_frag_cache), not per executor.
        self._frag_cache = _shared_frag_cache(
            tsdb.store,
            int(cfg.qcache_fragments),
            int(cfg.qcache_points))
        # Candidate-series hint per (metric, filter): identity hashes
        # and the series keys they were made from, out of the sketch
        # directory, revalidated on the metric's directory growth;
        # cost-bounded in total cached series (an unfiltered hint for
        # a high-cardinality metric is a multi-MB array and key list).
        self._ident_cache = LRUCache(256, max_cost=1 << 21)
        # Devwindow caches (previously ad-hoc dicts with wholesale
        # clear-at-cap eviction).
        self._dw_mask_cache = LRUCache(128)
        self._dw_plan_cache = LRUCache(128)
        self._dw_stage_cache = LRUCache(4)
        # The sharded window's stages: (device, programs' statics, the
        # window's chunk shape classes) whose programs a shard's device
        # has compiled (_dw_warm_shards). One forgotten is warmed again,
        # from jit's own cache.
        self._dw_shard_warm = LRUCache(256)
        # Fused-block stage cache (compress/): device grids keyed by
        # the generation set + range + downsample plan. Entries pin
        # their source SSTable objects so id() reuse can't alias a
        # dropped generation; eligibility (dirty range, format mix) is
        # re-checked per query — only the decode+stage compute caches.
        self._fused_stage_cache = LRUCache(4)
        # What a gather of the fused plan would else work out anew: the
        # selector's verdict a series key, by (metric, filter), and a
        # series' tags by name.
        self._fused_sel_memo = LRUCache(64)
        self._fused_named: dict[bytes, dict[str, str]] = {}
        # A gather's groups as its answer takes them (_GridGroups, the
        # labels kept), by what the groups are a function of: (metric,
        # filter) as the key, the gather's series directory in the
        # value, as the generation is in _dw_plan_cache's.
        self._fused_plan_cache = LRUCache(64)
        # Device-side decoded-block cache (compress/devcache.py):
        # per-block query-independent columns stay resident on device,
        # bounded by total cached points. Keyed by SSTable OBJECT +
        # block index (entries pin their generation against id reuse).
        dbp = int(cfg.devblock_points)
        self._devcache = None
        if dbp > 0 and self.backend != "cpu":
            from opentsdb_tpu.compress.devcache import DeviceBlockCache
            self._devcache = DeviceBlockCache(dbp)
        # Approx-serving rail cache (sketch/serving.py): per-series
        # (bucket_ts, est, lo, hi) rails for CLEAN fully-window-
        # covered percentile ranges, revalidated against the tier's
        # fold/refresh stamps. Cost = cached buckets.
        self._sketch_rail_cache = LRUCache(16, max_cost=1 << 22)
        self.qcache_hits = 0
        self.qcache_misses = 0
        self.qcache_bypasses = 0

    # ------------------------------------------------------------------
    # Planning: scan + span assembly + grouping
    # ------------------------------------------------------------------

    def _build_regexp(self, exact: list[tuple[bytes, bytes]],
                      group_bys: list[tuple[bytes, list[bytes] | None]],
                      prefix: int = UID_WIDTH + TIMESTAMP_BYTES,
                      ) -> bytes | None:
        """Row-key regexp over raw UID bytes, merged in tagk-id order.

        Parity: reference TsdbQuery.createAndSetFilter (:433-492).
        ``prefix`` is the byte count before the tag pairs — row keys
        carry base-time bytes after the metric; series keys (sketch
        directory) don't, so they pass UID_WIDTH."""
        if not exact and not group_bys:
            return None
        tagsize = 2 * UID_WIDTH
        items = []  # (tagk_uid, regex fragment)
        for k, v in exact:
            items.append((k, re.escape(k + v)))
        for k, values in group_bys:
            if values is None:
                frag = re.escape(k) + b".{%d}" % UID_WIDTH
            else:
                alts = b"|".join(re.escape(k + v) for v in sorted(values))
                frag = b"(?:" + alts + b")"
            items.append((k, frag))
        items.sort(key=lambda kv: kv[0])
        buf = b"(?s)^.{%d}" % prefix
        for _, frag in items:
            buf += b"(?:.{%d})*" % tagsize + frag
        buf += b"(?:.{%d})*$" % tagsize
        return buf

    def _tag_filters(self, tags: dict[str, str]):
        """Resolve a tag-filter map to UID-level (exact, group_bys)."""
        exact: list[tuple[bytes, bytes]] = []
        group_bys: list[tuple[bytes, list[bytes] | None]] = []
        for name, value in tags.items():
            k = self.tsdb.tagk.get_id(name)
            if value == "*":
                group_bys.append((k, None))
            elif "|" in value:
                vals = [self.tsdb.tagv.get_id(v) for v in value.split("|")]
                group_bys.append((k, vals))
            else:
                exact.append((k, self.tsdb.tagv.get_id(value)))
        return exact, group_bys

    def _find_series(self, spec: QuerySpec, start: int, end: int,
                     info: dict | None = None) -> _Scan:
        """Scan the matching rows into flat blocks and file the series
        with a point in [start, end] under the distinct combinations of
        their group-by tag values: a _Scan, of which no point has been
        copied yet. ``info``, when given, receives {"cached": bool} —
        True iff every fragment of the range served from the warm
        cache — and what was read: "rows" (storage rows decoded; a
        cache hit decodes none) and "points" (points in range, handed
        on to the group stage)."""
        metric_uid = self.tsdb.metrics.get_id(spec.metric)
        exact, group_bys = self._tag_filters(spec.tags)
        group_by_keys = sorted(k for k, _ in group_bys)
        regexp = self._build_regexp(exact, group_bys)

        blocks = self._scan_blocks(metric_uid, exact, group_bys,
                                   regexp, start, end, info)
        # What is left of a scan after its chunk.decode children: every
        # block's series cut to the exact bounds (two binary searches
        # over all of a block's series at once: codec.SeriesBlock.cut),
        # and those with a point named and filed under their group, from
        # the series keys alone.
        with obs_trace.span("scan.group") as sp:
            scan = _Scan(blocks, start, end)
            for i, skey in enumerate(scan.keys):
                tag_uids = codec.series_tag_uids(skey)
                scan.tags.append({
                    self.tsdb.tagk.get_name(k): self.tsdb.tagv.get_name(v)
                    for k, v in tag_uids.items()})
                gkey = tuple(tag_uids.get(k, b"") for k in group_by_keys)
                scan.groups.setdefault(gkey, []).append(i)
            if sp is not None:
                found = set()   # series with a stored point
                for blk in blocks:
                    found.update(itertools.compress(
                        blk.series_keys, np.diff(blk.bounds) > 0))
                sp.tags.update(series=len(found), groups=len(scan.groups))
        if info is not None:
            info["points"] = scan.points
        return scan

    def _find_spans(self, spec: QuerySpec, start: int, end: int,
                    info: dict | None = None) -> dict[tuple, list[_Span]]:
        """``_find_series`` as per-series columnar spans by group, for
        the callers that work a series at a time."""
        return self._find_series(spec, start, end, info).spans()

    # -- fragment cache (the query fast path) --------------------------

    def _series_hint(self, metric_uid: bytes, exact, group_bys,
                     ) -> dict:
        """Every KNOWN series matching the selector, as the keyword
        arguments scan_series hands the store: ``series_hint``, their
        uint64 identity hashes, which prune the storage fan-out (shard
        routing + per-generation series blooms), and ``series_keys``,
        which let a selective scan seek its rows by point lookup in
        place of listing the range (MemKVStore.scan_raw). Sourced from
        the streaming-sketch slot directory, which the WRITER's ingest
        path keeps a complete superset of series with stored data
        (TSDB.add_batch/add_point register via note_series BEFORE the
        put, so no query can observe stored rows the directory lacks):
        the pruning and the seek both rest on that and on nothing
        weaker. Empty — absence of a hint never prunes, and the
        scan walks — when sketches are disabled, nothing matches,
        or the store is a read-only replica: a replica's directory
        reloads only on checkpoint rebuilds, so it can lag
        WAL-suffix-replayed new series by a whole checkpoint
        interval."""
        sk = getattr(self.tsdb, "sketches", None)
        if sk is None or getattr(self.tsdb.store, "read_only", False):
            return {}
        fkey = (metric_uid, _filter_key(exact, group_bys))
        # Revalidate on THIS metric's directory size (monotonic): a new
        # series under another metric leaves the cached hint valid, and
        # a rebuild touches only this metric's keys.
        count = sk.metric_series_count(metric_uid)
        ent = self._ident_cache.get(fkey)
        if ent is not None and ent[0] == count:
            return ent[1]
        regexp = self._build_regexp(exact, group_bys, prefix=UID_WIDTH)
        pattern = re.compile(regexp, re.S) if regexp else None
        keys = [k for k in sk.metric_series_keys(metric_uid)
                if pattern is None or pattern.match(k)]
        hint = {"series_hint": np.asarray(
                    [series_hash(k) for k in keys], np.uint64),
                "series_keys": keys} if keys else {}
        self._ident_cache.put(fkey, (count, hint),
                              cost=max(len(keys), 1))
        return hint

    def _scan_chunk(self, metric_uid: bytes, regexp, hint,
                    c_lo: int, c_hi: int,
                    info: dict | None) -> codec.SeriesBlock:
        """Scan + decode one [c_lo, c_hi) base-time chunk into one flat
        block (the cacheable fragment unit)."""
        start_key = metric_uid + _u32(c_lo)
        stop_key = metric_uid + _u32(min(c_hi, 0xFFFFFFFF))
        return self.tsdb.scan_block(start_key, stop_key,
                                    key_regexp=regexp, counts=info,
                                    **hint)

    def _scan_selector(self, metric_uid: bytes, exact, group_bys,
                       regexp, start: int, end: int,
                       info: dict | None = None) -> dict:
        """``_scan_blocks`` as per-series Columns: views of the range's
        one block, or of its several merged."""
        return codec.SeriesBlock.merged(self._scan_blocks(
            metric_uid, exact, group_bys, regexp, start, end,
            info)).per_series()

    def _scan_blocks(self, metric_uid: bytes, exact, group_bys,
                     regexp, start: int, end: int,
                     info: dict | None = None,
                     ) -> list[codec.SeriesBlock]:
        """A selector's points over [start, end] as flat blocks in time
        order, one a fragment (full covering row range — the caller
        cuts to the exact bounds).

        The range splits into row-span-aligned chunks; each chunk
        serves from the fragment cache when (a) no shard has
        memtable-resident ("dirty") rows in it right now and (b) no
        base in it carries a row-create/remove transition stamp newer
        than the fragment (per-base stamps + replica-rebuild floor,
        MemKVStore.chunk_state — stamps outlive refcounts, so a
        create-then-delete that nets a chunk back to clean still
        invalidates fragments built during the window). Dirty chunks
        BYPASS the cache both ways — scanned fresh, never stored — so
        a live-ingest tail is re-read every time while frozen history
        hits RAM, and answers stay bit-identical to a cold scan:
        chunks align to the row span, so the chunks' blocks in turn
        hold every series' points in the whole-range decode's order.
        A fragment IS its block and nothing here copies it: the fused
        kernels' stream is written from the blocks as they lie
        (_Scan.stream), and only a consumer of per-series columns over
        several chunks pays for a merge (codec.SeriesBlock.merged)."""
        tsdb = self.tsdb
        cfg = tsdb.config
        store = tsdb.store
        # Query-path failpoint (fault/faultpoints.py): delay/raise
        # modes let tests stretch or break exactly the scan stage of a
        # traced query — the deterministic span-timing proof. Unarmed:
        # one empty-dict check per selector scan.
        _fault("query.scan")
        hint = self._series_hint(metric_uid, exact, group_bys)
        b_lo = codec.base_time(max(start, 0))
        b_hi = min(codec.base_time(min(end, 0xFFFFFFFF)), 0xFFFFFFFF)

        def full_scan() -> list[codec.SeriesBlock]:
            start_key = metric_uid + _u32(b_lo)
            stop_key = metric_uid + _u32(
                min(b_hi + MAX_TIMESPAN, 0xFFFFFFFF))
            with obs_trace.span("chunk.decode", outcome="unchunked"):
                return [tsdb.scan_block(start_key, stop_key,
                                        key_regexp=regexp, counts=info,
                                        **hint)]

        chunk_s = int(cfg.qcache_chunk_s or 0)
        chunk_s -= chunk_s % MAX_TIMESPAN
        state_fn = getattr(store, "chunk_state", None)
        if (not cfg.qcache or state_fn is None
                or chunk_s <= 0 or b_hi < b_lo):
            return full_scan()
        c0 = b_lo - b_lo % chunk_s
        nchunks = (b_hi - c0) // chunk_s + 1
        if nchunks > int(cfg.qcache_max_chunks):
            # All-time-style ranges: per-chunk scan setup would cost
            # more than it saves, and caching them would flush the
            # dashboard working set.
            return full_scan()
        table = tsdb.table
        # The table participates in the fragment key: the cache is
        # per-store and shared across executors, and two TSDB facades
        # over one store may serve different tables.
        fkey = (table, metric_uid, _filter_key(exact, group_bys))
        chunks = [c0 + i * chunk_s for i in range(nchunks)]
        # States read BEFORE each scan: content can only get newer
        # between the state read and the scan, so a racing mutation
        # stamps its bases past the fragment's tagged seq and the next
        # lookup conservatively invalidates — never the reverse.
        states = [state_fn(table, c, c + chunk_s) for c in chunks]
        if all(st[3] for st in states):
            # Nothing cacheable (all-memtable store / fully-hot range):
            # one unchunked scan beats per-chunk setup.
            self.qcache_bypasses += nchunks
            if info is not None:
                info["cached"] = False
            sp = obs_trace.current_span()
            if sp is not None:
                sp.tags["qcache_bypass"] = (
                    sp.tags.get("qcache_bypass", 0) + nchunks)
            return full_scan()
        blocks = []
        all_hit = True
        n_hit = n_miss = n_byp = 0
        for c, (seqs, floors, stamps, dirty) in zip(chunks, states):
            key = (fkey, c, chunk_s)
            if dirty:
                self.qcache_bypasses += 1
                n_byp += 1
                all_hit = False
                with obs_trace.span("chunk.decode", outcome="bypass",
                                    base=int(c)):
                    frag = self._scan_chunk(metric_uid, regexp, hint,
                                            c, c + chunk_s, info)
            else:
                ent = self._frag_cache.get(key)
                if ent is not None and all(
                        e >= f and m <= e
                        for e, f, m in zip(ent[0], floors, stamps)):
                    self.qcache_hits += 1
                    n_hit += 1
                    frag = ent[1]
                else:
                    self.qcache_misses += 1
                    n_miss += 1
                    all_hit = False
                    with obs_trace.span("chunk.decode", outcome="miss",
                                        base=int(c)):
                        frag = self._scan_chunk(metric_uid, regexp,
                                                hint, c, c + chunk_s, info)
                    self._frag_cache.put(
                        key, (seqs, frag),
                        cost=max(len(frag.cols.timestamps), 1))
            blocks.append(frag)
        if info is not None:
            info["cached"] = all_hit
        # Fragment-cache outcome on the enclosing span (scan /
        # raw.stitch): accumulated, because one query scans several
        # selectors and stitch ranges. Cache HITS are ~free (a dict
        # get), so they get a count, not a span.
        sp = obs_trace.current_span()
        if sp is not None:
            t = sp.tags
            t["qcache_hit"] = t.get("qcache_hit", 0) + n_hit
            t["qcache_miss"] = t.get("qcache_miss", 0) + n_miss
            t["qcache_bypass"] = t.get("qcache_bypass", 0) + n_byp
        return blocks

    @staticmethod
    def _group_tags(members: list[dict[str, str]]):
        """Intersection tags + aggregated (differing) tag names of a
        group, from its series' named tags.

        Parity: reference SpanGroup.computeTags (:149-173)."""
        common = dict(members[0])
        keys = set(members[0])
        for tags in members[1:]:
            keys &= set(tags)
            for k in list(common):
                if tags.get(k) != common[k]:
                    del common[k]
        common = {k: v for k, v in common.items() if k in keys}
        aggregated = sorted(
            {k for tags in members for k in tags} - set(common))
        return common, aggregated

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------

    def run(self, spec: QuerySpec, start: int, end: int,
            ) -> list[QueryResult]:
        return self.run_with_plan(spec, start, end)[0]

    def run_with_plan(self, spec: QuerySpec, start: int, end: int,
                      trace=None, rollup_only: bool = False,
                      ) -> tuple[list[QueryResult], str, bool]:
        """run() plus the planner-choice label for THIS call ("raw",
        "resident", or a rollup resolution like "1h") and whether the
        answer came ENTIRELY from the warm fragment cache. Returned
        rather than stashed on the executor so server threads sharing
        one executor can't read a neighbor query's labels.

        ``trace`` (obs/trace.Trace): when given, the execution stages
        — planner pick, rollup read / raw stitch, storage scan with
        per-shard fan-out and per-chunk decode, aggregation — record
        themselves as a span tree under ``trace.root``. None (the
        default) costs one global-int check per hook.

        ``rollup_only`` is the load-shedding ladder's degraded step
        (serve/admission.py): serve from the materialized tier with NO
        raw work — dirty/edge windows are omitted instead of stitched
        (the caller tags the result "degraded") — and raise
        OverloadedError for queries the tier cannot serve at all.
        Device-resident answers stay allowed: they're exact and
        storage-free."""
        if trace is None:
            results, plan, cached = self._run_planned(
                spec, start, end, rollup_only=rollup_only)
        else:
            with obs_trace.activate(trace):
                results, plan, cached = self._run_planned(
                    spec, start, end, rollup_only=rollup_only)
            trace.root.tags["plan"] = plan
            trace.root.tags["cached"] = bool(cached)
        self.last_plan = plan
        return results, plan, cached

    def run_approx(self, spec: QuerySpec, start: int, end: int,
                   trace=None, rollup_only: bool = False,
                   approx=None):
        """run_with_plan under the APPROXIMATE-SERVING contract
        (sketch/serving.py): returns ``(results, plan, cached,
        approx_info)`` where ``approx_info`` is None for an exact
        answer, an ``ApproxInfo`` for a sketch-served percentile
        downsample, or a dict describing degraded stale/omitted
        coverage (rollup-only mode over dirty windows).

        ``approx`` (ApproxSpec): the caller's opt-in + relative error
        budget. A percentile downsample serves from sketch columns
        when the caller opted in OR the ladder degraded
        (``rollup_only``); if the reported bound exceeds the budget,
        the exact path runs instead — except under rollup-only, where
        there IS no exact path and the query sheds with 503."""
        from opentsdb_tpu.sketch.serving import ApproxSpec
        if approx is None:
            approx = ApproxSpec()
        if trace is None:
            out = self._run_approx_inner(spec, start, end,
                                         rollup_only, approx)
        else:
            with obs_trace.activate(trace):
                out = self._run_approx_inner(spec, start, end,
                                             rollup_only, approx)
            trace.root.tags["plan"] = out[1]
            trace.root.tags["cached"] = bool(out[2])
            if out[3] is not None:
                trace.root.tags["approx"] = True
        self.last_plan = out[1]
        return out

    def _run_approx_inner(self, spec: QuerySpec, start: int, end: int,
                          rollup_only: bool, approx):
        ds_pct = bool(
            spec.downsample
            and Aggregators.get(spec.downsample[1]).kind
            == "percentile")
        if ds_pct and (approx.enabled or rollup_only):
            from opentsdb_tpu.sketch import serving as _serving
            got = _serving.plan_percentile(self, spec, start, end,
                                           rollup_only=rollup_only)
            if got is not None:
                results, res, info = got
                if (approx.max_error is None
                        or info.rel_error <= approx.max_error):
                    from opentsdb_tpu.rollup.tier import res_label
                    return (results, f"approx-{res_label(res)}",
                            False, info)
                _serving._M_FALLBACK.inc()
            if rollup_only:
                from opentsdb_tpu.core.errors import OverloadedError
                raise OverloadedError(
                    "shedding load: no approximate answer within the "
                    "error budget for this percentile query; retry "
                    "shortly", retry_after=0.5, status=503)
        meta: dict = {}
        results, plan, cached = self._run_planned(
            spec, start, end, rollup_only=rollup_only, meta_out=meta)
        info = None
        if rollup_only and (meta.get("stale_windows")
                            or meta.get("omitted_edges")
                            or meta.get("missing_windows")):
            # Rollup-only over a dirty range: stale windows were
            # SERVED (their records reflect the last fold), edge
            # windows omitted, never-folded dirty windows ABSENT —
            # all declared, never silent.
            info = {"kind": "rollup-stale",
                    "stale_windows": int(meta.get("stale_windows", 0)),
                    "omitted_edges": int(meta.get("omitted_edges", 0)),
                    "missing_windows": int(
                        meta.get("missing_windows", 0)),
                    "error": None}
        return results, plan, cached, info

    # -- expert-parallel dashboard batches ----------------------------

    def run_expert_batch(self, specs: "list[QuerySpec]", start: int,
                         end: int):
        """Serve a whole mixed dashboard batch in ONE mesh dispatch.

        With a mesh configured (Config.mesh_shape) and expert serving
        on (Config.expert_parallel), heterogeneous `/q` sub-queries —
        mixed sum/avg/dev panels and pNN percentile panels — pack into
        expert buckets (parallel/expert.py run_dashboard_batch): the
        mesh partitions by aggregator family and every family's slots
        run concurrently under one program, so a mixed batch costs
        ~max(family) wall-clock instead of sum(sub-queries). Answers
        match the serial leg's fused kernels (f32 tolerance: group
        sums reduce in a shared-padding order).

        Returns ``(per_spec_results, None)`` on success or
        ``(None, reason)`` on a DECLINE — the caller reports the
        decline (`plan: "expert-decline"` per result + counter, the
        TSINT fused-decline discipline) and runs the serial leg.
        Declines are exact-or-fall-back, never approximate: ragged
        intervals, rate/no-lerp aggregators, non-moment downsamplers,
        int32-unsafe ranges all fall off the path loudly.
        """
        from opentsdb_tpu.parallel.expert import DASH_AGG_ID
        if self.mesh is None:
            return None, "no-mesh"
        if int(self.mesh.devices.size) < 2:
            return None, "single-device-mesh"
        if self.backend == "cpu":
            return None, "cpu-backend"
        if len(specs) < 2:
            return None, "single-query"
        if end <= start:
            raise BadRequestError(
                f"end time {end} is <= start time {start}")
        intervals = set()
        for spec in specs:
            if not spec.downsample:
                return None, "no-downsample"
            interval, dsagg = spec.downsample
            ds = NOLERP_AGGS.get(dsagg, dsagg)
            if (Aggregators.get(dsagg).kind != "moment"
                    or ds not in DASH_AGG_ID):
                return None, "downsampler"
            agg = Aggregators.get(spec.aggregator)
            if agg.kind == "moment":
                if (spec.aggregator in NOLERP_AGGS
                        or spec.aggregator not in DASH_AGG_ID):
                    # The no-lerp family skips gap filling; the dash
                    # kernel is the lerp family only.
                    return None, "no-lerp-agg"
            elif agg.kind != "percentile":
                return None, "agg-family"
            if spec.rate:
                return None, "rate"
            intervals.add(interval)
        if len(intervals) != 1:
            # Mixed downsample intervals = ragged bucket grids: slots
            # must share one static [S, B] layout.
            return None, "ragged-intervals"
        interval = intervals.pop()
        qbase = start - start % interval
        if end - qbase > 2**31 - 1:
            return None, "range"
        num_buckets = _pad_size(int((end - qbase) // interval + 1))
        per_spec_groups = []
        s_max = 1
        for spec in specs:
            with obs_trace.span("scan"):
                groups = self._find_spans(spec, start, end)
            per_spec_groups.append(groups)
            for spans in groups.values():
                s_max = max(s_max, len(spans))
        S = _pad_size(s_max)
        if S * num_buckets >= 2**31:
            return None, "grid"
        queries = []
        refs = []
        for si, (spec, groups) in enumerate(zip(specs,
                                                per_spec_groups)):
            _, dsagg = spec.downsample
            ds = NOLERP_AGGS.get(dsagg, dsagg)
            agg = Aggregators.get(spec.aggregator)
            for gkey in sorted(groups):
                spans = groups[gkey]
                rel, vals, sid, valid = self._flatten_spans(spans,
                                                            qbase)
                qq = {"family": ("percentile"
                                 if agg.kind == "percentile"
                                 else "moment"),
                      "ts": rel, "vals": vals, "sid": sid,
                      "dsagg": ds}
                if agg.kind == "percentile":
                    qq["quantile"] = agg.quantile
                else:
                    qq["agg"] = spec.aggregator
                queries.append(qq)
                refs.append((si, spans))
        per_spec: list[list[QueryResult]] = [[] for _ in specs]
        if not queries:
            return per_spec, None
        from opentsdb_tpu.parallel.expert import run_dashboard_batch
        with obs_trace.span("aggregate"):
            got = run_dashboard_batch(
                queries, self.mesh, num_series=S,
                num_buckets=num_buckets, interval=interval)
        for (si, spans), (gv, gm) in zip(refs, got):
            tags, aggregated = self._group_tags(
                [sp.tags for sp in spans])
            mask = np.asarray(gm)
            grid_ts = (np.flatnonzero(mask).astype(np.int64) * interval
                       + qbase)
            per_spec[si].append(QueryResult(
                specs[si].metric, tags, aggregated, grid_ts,
                np.asarray(gv)[mask].astype(np.float64)))
        return per_spec, None

    def _run_planned(self, spec: QuerySpec, start: int, end: int,
                     rollup_only: bool = False,
                     meta_out: dict | None = None,
                     ) -> tuple[list[QueryResult], str, bool]:
        if end <= start:
            raise BadRequestError(
                f"end time {end} is <= start time {start}")
        agg = Aggregators.get(spec.aggregator)
        if agg.kind == "cardinality":
            raise BadRequestError(
                "use distinct_tagv() / the /distinct endpoint for "
                "cardinality queries")
        # Rollup planner step: serve window-aligned downsamples from
        # the materialized summary tier (rollup/planner.py), with raw
        # stitching over edge/dirty windows. The returned spans are
        # already per-bucket values, so the rewritten spec's downsample
        # stage is the identity and the shared group stage below runs
        # unchanged on either backend. The "planner.pick" span covers
        # the whole resolution decision INCLUDING the tier reads and
        # raw stitches it triggers (they appear as child spans), so a
        # trace's top-level children tile the query wall time.
        with obs_trace.span("planner.pick") as sp:
            dev = self._run_devwindow(spec, start, end, agg)
            planned = None
            fusedr = None
            if dev is None:
                planned = self._plan_rollup(spec, start, end,
                                            rollup_only=rollup_only,
                                            meta_out=meta_out)
            if dev is None and planned is None and rollup_only:
                from opentsdb_tpu.core.errors import OverloadedError
                raise OverloadedError(
                    "shedding load: this query needs a raw scan "
                    "(no eligible rollup resolution); retry shortly",
                    retry_after=0.5, status=503)
            if dev is None and planned is None:
                # Fused decode-plus-aggregate off TSST4 blocks
                # (compress/): tried after the materialized tiers
                # (resident window, rollups beat re-deriving from
                # storage) and before the raw scan. Exact or None.
                fusedr = self._run_fused_blocks(spec, start, end, agg)
            if sp is not None:
                if dev is not None:
                    sp.tags["plan"] = "resident"
                elif planned is not None:
                    from opentsdb_tpu.rollup.tier import res_label
                    sp.tags["plan"] = res_label(planned[2])
                elif fusedr is not None:
                    sp.tags["plan"] = "fused"
                else:
                    sp.tags["plan"] = "raw"
        if dev is not None:
            return dev, "resident", False
        if planned is not None:
            groups, spec2, res = planned
            from opentsdb_tpu.rollup.tier import res_label
            with obs_trace.span("aggregate"):
                results = self._execute_groups(
                    spec2, _Scan.of_spans(groups), start, end)
            return results, res_label(res), False
        if fusedr is not None:
            return fusedr, "fused", False
        import time as _time
        t0 = _time.time()
        info: dict = {}
        with obs_trace.span("scan") as sp:
            scan = self._find_series(spec, start, end, info)
            if sp is not None:
                sp.tags.update(cached=bool(info.get("cached")),
                               rows=info.get("rows", 0),
                               points=info["points"])
        _C_RAW_ROWS.inc(info.get("rows", 0))
        _C_RAW_POINTS.inc(info["points"])
        self.scan_latency.add((_time.time() - t0) * 1000)
        with obs_trace.span("aggregate"):
            results = self._execute_groups(spec, scan, start, end)
        return results, "raw", bool(info.get("cached"))

    def _plan_rollup(self, spec: QuerySpec, start: int, end: int,
                     rollup_only: bool = False,
                     meta_out: dict | None = None):
        if getattr(self.tsdb, "rollups", None) is None:
            return None
        from opentsdb_tpu.rollup import planner
        return planner.plan(self, spec, start, end,
                            rollup_only=rollup_only,
                            meta_out=meta_out)

    def _execute_groups(self, spec: QuerySpec, scan: _Scan,
                        start: int, end: int) -> list[QueryResult]:
        """Group-stage execution shared by the raw-scan and rollup
        paths (identical inputs => identical answers, the golden-parity
        contract of tests/test_rollup.py)."""
        gkeys = sorted(scan.groups)
        # Ranges wider than int32 seconds (>68 years, e.g. start=0
        # "all-time" against year-2106 timestamps) would wrap the int32
        # rel-timestamp offsets the kernels use; the float64 oracle
        # serves them instead (they are rare and scan-bound anyway).
        use_cpu = self.backend == "cpu"
        if not use_cpu and spec.downsample and Aggregators.get(
                spec.downsample[1]).kind == "percentile":
            # Percentile DOWNSAMPLERS (1h-p95) run on the float64
            # oracle: the fused device kernels reduce moments, not
            # per-bucket order statistics. (The approximate sketch
            # path is the fast answer; this is the exact one.)
            use_cpu = True
        if not use_cpu:
            qbase = (start - start % spec.downsample[0]
                     if spec.downsample else start)
            use_cpu = end - qbase > 2**31 - 1
        # A downsampled request on the TPU backend is ONE fused kernel
        # call over the scan's whole point stream, however many groups
        # (two segment reductions for all of them — or the grouped
        # radix select for percentiles) instead of G calls. Its callers
        # open the children of the enclosing "aggregate" span (README,
        # "Observability"): aggregate.pack / .dispatch / .wait /
        # .fetch, then .results.
        if not use_cpu and spec.downsample:
            if len(gkeys) > 1:
                per_group = self._run_tpu_multigroup(spec, scan, gkeys,
                                                     start, end)
            else:
                per_group = [self._tpu_downsample_group(spec, scan,
                                                        start, end)
                             for _ in gkeys]
        else:
            # A series at a time: the float64 oracle, or union-grid
            # interpolation on the device.
            spans = scan.spans()
            run = self._run_cpu if use_cpu else self._run_tpu
            per_group = [run(spec, spans[k], start) for k in gkeys]
        with obs_trace.span("aggregate.results", results=len(gkeys)):
            return [QueryResult(
                        spec.metric,
                        *self._group_tags([scan.tags[i]
                                           for i in scan.groups[gkey]]),
                        ts, vals)
                    for gkey, (ts, vals) in zip(gkeys, per_group)]

    # -- device-resident window path ----------------------------------

    def _run_devwindow(self, spec: QuerySpec, start: int, end: int,
                       agg) -> list[QueryResult] | None:
        """Serve the query from the device-resident hot window
        (storage/devstore.py) when it exactly covers [start, end]: no
        storage scan, no host->device point upload — the host only
        filters the series directory and uploads an [S]-sized group map.
        Returns None to fall back to the scan path (CPU backend,
        un-downsampled queries, dirty/evicted windows, unknown UIDs,
        out-of-int32 epochs/ranges)."""
        dw = getattr(self.tsdb, "devwindow", None)
        # A mesh executor serves the resident path only through the
        # mesh-SHARDED window (devshard.py): the plain single-device
        # window under a mesh keeps declining as before (its columns
        # live on one device while the mesh plans expect sharding).
        sharded = hasattr(dw, "shard_of")
        if (dw is None or self.backend == "cpu"
                or (self.mesh is not None and not sharded)
                or not spec.downsample
                or agg.kind not in ("moment", "percentile")
                or Aggregators.get(spec.downsample[1]).kind
                != "moment"):
            return None
        interval, dsagg = spec.downsample
        qbase = start - start % interval
        imin, imax = -(2**31), 2**31 - 1
        # Rebased in-range timestamps span up to end - qbase; past int32
        # they would wrap in the kernels. Checked BEFORE touching the
        # window: dw.columns() forces a staged upload + drain, wasted on
        # a query that can never be served from it.
        if end - qbase > imax:
            return None
        from opentsdb_tpu.core.errors import NoSuchUniqueName
        try:
            metric_uid = self.tsdb.metrics.get_id(spec.metric)
            exact, group_bys = self._tag_filters(spec.tags)
        except NoSuchUniqueName:
            return None  # scan path raises the canonical error
        # The window serves queries from its raw chunk list (no
        # concatenated copy — the window can approach the whole HBM);
        # every moment family folds chunk-wise, dev included (Chan M2
        # combination, ops/kernels._chunk_fold).
        # From here the resident.* spans are the children of
        # planner.pick, in order and together tiling it (README,
        # "Observability", says what each one times).
        with obs_trace.span("resident.columns") as sp:
            cols = dw.chunk_columns(metric_uid, start, end)
            if sp is not None and cols is not None:
                chunks = _dw_chunks(cols)
                sp.tags["chunks"] = len(chunks)
                sp.tags["points"] = sum(int(c[0].shape[0])
                                        for c in chunks)
        if cols is None:
            # On planner.pick, the span open around this call: why the
            # window declined a request of a kind it serves.
            sp = obs_trace.current_span()
            if sp is not None:
                sp.tags["miss"] = dw.last_miss()
            return None
        with obs_trace.span("resident.groups") as gsp:
            groups, named, grid, plan_hit = self._devwindow_groups(
                dw, metric_uid, cols, exact, group_bys)
            if not groups:
                return []

            # The shift (qbase - epoch) participates in arithmetic on
            # device (rel_ts - shift in window_series_stage) — unlike
            # lo/hi, which are comparison-only and clamp safely. If it
            # doesn't fit in int32 (e.g. an all-time query against a
            # metric whose epoch is past 2^31), fall back to the scan
            # path rather than silently mis-bucketing (devstore's
            # exact-or-fall-back contract). Sharded windows carry one
            # epoch PER shard; all must fit.
            epochs = ([sc.epoch for sc in cols.shards if sc is not None]
                      if sharded else [cols.epoch])
            if not all(imin <= qbase - e <= imax for e in epochs):
                return None
            num_buckets = _pad_size(int((end - qbase) // interval + 1))
            S_all = len(cols.series_keys)
            S_pad = _pad_size(S_all)
            if S_pad * num_buckets > kernels.STAGE_GRID_MAX:
                # The largest grid the daemon kept room for beside the
                # window at boot (tools/cli.py), and far under where the
                # kernels' int32 per-(series, bucket) segment ids would
                # wrap. Scan path handles it (per-group kernels, smaller
                # grids).
                return None
            gkeys = grid.gkeys
            G = _pad_size(len(gkeys))
            # Device-resident include/gmap, cached per (window instance,
            # plan, generation, padding): every fresh host array argument
            # is its own transfer, so repeat dashboard queries should not
            # re-upload masks that only change when the series directory
            # grows (generation bump invalidates;
            # instance_id guards against a replacement window whose counters
            # restart at 0 — devstore's cache-keying contract).
            mask_cache = self._dw_mask_cache
            fk = _filter_key(exact, group_bys)
            mkey = (dw.instance_id, metric_uid, fk)
            hit = mask_cache.get(mkey)
            if hit is not None and hit[0] == cols.generation:
                include, gmap, sids = hit[1:]
            else:
                include = np.zeros(S_pad, bool)
                gmap = np.full(S_pad, G - 1, np.int32)
                for gi, gkey in enumerate(gkeys):
                    for sid in groups[gkey]:
                        include[sid] = True
                        gmap[sid] = gi
                # Sharded window: commit to the combine device (the first
                # owning shard's) so the apply's inputs are colocated with
                # the gathered stage grids.
                tgt = None
                if sharded:
                    for sc in cols.shards:
                        if sc is not None and sc.chunks:
                            try:
                                tgt = next(iter(sc.chunks[0][0].devices()))
                            except Exception:
                                tgt = None
                            break
                # The matched series ids, sorted: what the stage's block
                # selection is narrowed by.
                sids = np.flatnonzero(include)
                include = jax.device_put(include, tgt)
                gmap = jax.device_put(gmap, tgt)
                # Generation lives in the VALUE (the _dw_plan_cache
                # pattern): a directory growth overwrites in place, so dead
                # generations never accumulate device arrays.
                mask_cache.put(mkey,
                               (cols.generation, include, gmap, sids))
            if gsp is not None:
                gsp.tags.update(series=S_all, groups=len(gkeys),
                                plan_hit=plan_hit,
                                mask_hit=hit is not None
                                and hit[0] == cols.generation)
        ngroups = 1 if len(gkeys) == 1 else G
        rate_kw = self._rate_kw(spec)
        # The heavy N-point half of a window query (range mask +
        # per-series downsample [+ rate]) caches per (window instance,
        # metric, data version, range, interval, downsample, rate, the
        # filter its blocks were narrowed by) and stays device-resident.
        # A request whose matched series cut no block out (it matched
        # every series, or every block in range holds one of them)
        # folds the blocks of its range whole: that stage is good for
        # any tag filter, any group-by, moments and p50/p95/p99 alike,
        # which then pay only the [S, B]-sized apply + one dispatch.
        # Any other folds only the blocks its series can lie in
        # (DevChunks.narrowed): its grids are whole for the rows its
        # own include mask keeps and partial for the others, so its
        # stage answers that filter alone.
        cache = self._dw_stage_cache
        with obs_trace.span("resident.stage") as ssp:
            whole, cols = cols, cols.narrowed(sids, start, end)
            narrowed = cols is not whole
            skey = (dw.instance_id, metric_uid, cols.version, start, end,
                    interval, dsagg, tuple(sorted(rate_kw.items())),
                    fk if narrowed else None)
            stage = cache.get(skey)
            (_C_STAGE_MISS if stage is None else _C_STAGE_HIT).inc()
            if ssp is not None:
                ssp.tags["hit"] = stage is not None
                ssp.tags["narrowed"] = narrowed
                ssp.tags["series"] = len(sids)
            if stage is None:
                (_C_FOLD_NARROWED if narrowed else _C_FOLD_WHOLE).inc()
                picked, of, visited, resident, folded, calls, programs = \
                    _dw_fold_extent(cols)
                _C_FOLD_VISITED.inc(visited)
                _C_FOLD_SKIPPED.inc(resident - visited)
                _C_FOLD_DISPATCHES.inc(calls)
                _C_STAGE_PROGRAMS.inc(programs)
                if ssp is not None:
                    ssp.tags["chunks"] = folded
                    ssp.tags["calls"] = calls
                    ssp.tags["blocks"] = picked
                    ssp.tags["blocks_total"] = of
                try:
                    if sharded:
                        grids = self._dw_sharded_stage(
                            (dw.instance_id, metric_uid), cols, start,
                            end, qbase, num_buckets=num_buckets,
                            S_pad=S_pad, interval=interval, dsagg=dsagg,
                            rate_kw=rate_kw)
                        if grids is None:
                            return None
                    else:
                        lo32 = np.int32(
                            min(max(start - cols.epoch, imin), imax))
                        hi32 = np.int32(
                            min(max(end - cols.epoch, imin), imax))
                        shift32 = np.int32(qbase - cols.epoch)
                        grids = kernels.window_series_stage_chunks(
                            cols.chunks, lo32, hi32, shift32,
                            num_series=S_pad, num_buckets=num_buckets,
                            interval=interval, agg_down=dsagg,
                            blocks=cols.blocks, block=cols.block,
                            **rate_kw)
                        _FOLD_HANDED.add(grids[5])
                except Exception as e:
                    # A near-HBM window can still OOM building the stage
                    # grids; degrade to the storage scan (the
                    # exact-or-fall-back contract) instead of erroring.
                    if _is_device_oom(e):
                        return None
                    raise
                # [5] fills with the host copy of presence on first fetch.
                stage = list(grids[:5]) + [None]
                # Stages of this metric's EARLIER data versions can never
                # hit again (version is monotonic) but each pins [S, B]
                # grids in HBM the devwindow's own budget can't see — drop
                # them before the LRU cap so active ingest (a version bump
                # per flush) doesn't strand dead grids on device.
                for k in cache.keys():
                    if k[:2] == (dw.instance_id, metric_uid) \
                            and k[2] != cols.version:
                        cache.pop(k)
                        _C_STAGE_EVICTED.inc()
                cache.put(skey, stage)
        sv, sm, filled, in_range, presence_dev = stage[:5]
        # Shrink-wrap the fetch: clip to the live group/bucket counts
        # (64-quantized so statics don't churn recompiles) and bit-pack
        # the mask on device, so wide group-by queries do not fetch
        # padded [G, B] grids (what the fetch costs is the ledger's
        # fetch_ms).
        b_live = int((end - qbase) // interval + 1)
        g_out = min(ngroups, _pad64(len(gkeys)))
        b_out = min(num_buckets, _pad64(b_live))
        shrink = dict(g_out=g_out, b_out=b_out,
                      wire_bf16=bool(self.tsdb.config.wire_bf16))
        # The applies allocate fresh [S,B]/[G,B] buffers on a device the
        # resident window may have filled to within a few hundred MB of
        # HBM — an OOM here (or in the fetch's staging buffer) must
        # degrade to the scan path exactly like a stage-build OOM, or
        # the exact-or-fall-back contract breaks precisely in the
        # 1B-resident regime it exists for.
        try:
            with obs_trace.span("resident.apply", g_out=g_out,
                                b_out=b_out):
                if agg.kind == "percentile":
                    gv, gm = kernels.window_quantile_apply(
                        sm, filled, in_range, include, gmap,
                        np.array([agg.quantile], np.float32),
                        num_groups=ngroups, **shrink)
                else:
                    gv, gm = kernels.window_moment_apply(
                        sv, sm, filled, in_range, include, gmap,
                        num_groups=ngroups, agg_group=spec.aggregator,
                        **shrink)
            if obs_trace.current_span() is not None:
                # Traced only: stage and apply above are dispatches
                # (JAX returns before the device finishes), so without
                # this sync the device's time would all land in the
                # fetch. Untraced the path makes no such call.
                with obs_trace.span("resident.wait"):
                    jax.block_until_ready((gv, gm))
            # Series with no in-range points must not shape group labels
            # or emit empty groups — match the scan path, which never
            # sees them. (Pre-rate presence: computed from the raw
            # in-range mask, like the scan path's "series exists".) One
            # batched device_get — separate np.asarray fetches would
            # each pay a transport round trip; presence is fetched once
            # per stage.
            with obs_trace.span("resident.fetch") as sp:
                if stage[5] is None:
                    gv, gm, stage[5] = jax.device_get(
                        (gv, gm, presence_dev))
                else:
                    gv, gm = jax.device_get((gv, gm))
                if sp is not None:
                    sp.tags["bytes"] = int(gv.nbytes + gm.nbytes)
        except Exception as e:
            if _is_device_oom(e):
                # Drop the stage too: leaving it cached would pin its
                # [S, B] grids in the very HBM that just ran out, and
                # every later query of this panel would re-dispatch a
                # doomed apply before falling back.
                if cache.pop(skey, None) is not None:
                    _C_STAGE_EVICTED.inc()
                return None
            raise
        with obs_trace.span("resident.results") as sp:
            results = _grid_results(spec.metric, grid, named.__getitem__,
                                    stage[5], gv, gm, b_out, interval,
                                    qbase)
            if sp is not None:
                sp.tags["results"] = len(results)
        return results

    def _dw_sharded_stage(self, of: tuple, cols, start: int, end: int,
                          qbase: int, *, num_buckets: int, S_pad: int,
                          interval: int, dsagg: str, rate_kw: dict):
        """The stage half of a resident query over the mesh-SHARDED
        hot set (storage/devshard.py): each shard's chunk fold runs on
        its OWN device (async dispatch overlaps the shards), then only
        the [S_shard, B] stage grids — never the N-point columns —
        travel to the first shard's device, where one program
        (kernels.shard_combine) lays their rows out in
        combined-directory order, padded to S_pad. Row order equals
        ``cols.series_keys`` order, so include/gmap and the apply
        kernels are oblivious to sharding. ``of``: (window instance,
        metric), which with ``cols.generation`` names the directory.

        Nothing here compiles for a metric, a host or a range of its
        own: every shard folds into grids of one padded height (that of
        the fullest), on its own device whether or not a block of its
        chunks was picked, and which rows the join takes from where is
        an array. What a request of some kind compiles, the first
        request of that kind has compiled, on every device: a program
        belongs to one device, the shard a one-host panel folds on
        follows the host it drew, and a metric's series fall to the
        shards in their own numbers.

        Numeric contract (declared, README "Serving mesh"): the
        per-shard folds are the SAME f32 kernels as the 1-shard path
        and a series never splits across shards, so count/min/max rows
        are byte-identical across shard counts while sum/avg/dev rows
        agree to f32 tolerance (bucket partial sums reassociate across
        chunk boundaries that fall differently per shard).

        Returns the window_series_stage grid tuple, or None when some
        shard's epoch shift cannot represent in int32 (scan fallback,
        checked again here because the caller's probe reads the shards
        it captured — a reshard between the two is benign either way).
        """
        imin, imax = -(2**31), 2**31 - 1
        live = [(i, sc) for i, sc in enumerate(cols.shards)
                if sc is not None]
        if not live or not all(imin <= qbase - sc.epoch <= imax
                               for _i, sc in live):
            return None
        held = [len(sc.series_keys) for _i, sc in live]
        height = _pad_size(max(held))
        statics = dict(num_series=height, num_buckets=num_buckets,
                       interval=interval, agg_down=dsagg, **rate_kw)
        programs = tuple(sorted(statics.items()))

        # A shard's device compiles what a request of this kind can run
        # there before the first stage of the kind is built, whichever
        # shard that request's own selection folds on.
        cold = []
        for i, _sc in live:
            window = cols.shard_windows[i]
            warm = (_device_id(window.device), programs,
                    window.chunk_sizes)
            if self._dw_shard_warm.get(warm) is None:
                cold.append((warm, window))
        if cold:
            self._dw_warm_shards(cold, live[0][1].block, statics)
        parts = []
        for i, sc in live:
            window = cols.shard_windows[i]
            # The host's time in this shard's stage: its start, its fold
            # dispatches, its finish (the device runs on behind it).
            with obs_trace.span("resident.shard", shard=i) as sp:
                grids = kernels.window_series_stage_chunks(
                    sc.chunks,
                    np.int32(min(max(start - sc.epoch, imin), imax)),
                    np.int32(min(max(end - sc.epoch, imin), imax)),
                    np.int32(qbase - sc.epoch),
                    blocks=sc.blocks, block=sc.block,
                    device=window.device, **statics)
                if sp is not None:
                    sp.tags.update(
                        device=_device_id(window.device),
                        series=len(sc.series_keys),
                        chunks=sum(len(b) > 0 for b in sc.blocks))
            _FOLD_HANDED.add(grids[5])
            parts.append(grids[:5])
        _C_STAGE_SHARDS.inc(len(parts))
        # What brings the shards' grids to the combine device (the
        # first shard's) and joins them: the copies between devices and
        # the one program that lays the rows out.
        with obs_trace.span("resident.gather", shards=len(parts)) as sp:
            target = next(iter(parts[0][0].devices()))
            moved = sum(g.nbytes for grids in parts for g in grids
                        if target not in g.devices())
            hit = self._dw_mask_cache.get(of + ("shard_rows",))
            if hit is not None and hit[:2] == (cols.generation, height):
                rows = hit[2]
            else:
                # Row r of the joined grids: the shard its series lives
                # in, times the height, plus the series' row there; a
                # padding row, one past every shard's.
                rows = np.full(S_pad, len(live) * height, np.int32)
                rows[:sum(held)] = np.concatenate(
                    [n * height + np.arange(mine)
                     for n, mine in enumerate(held)])
                rows = jax.device_put(rows, target)
                self._dw_mask_cache.put(
                    of + ("shard_rows",), (cols.generation, height, rows))
            outs = kernels.shard_combine(
                tuple(tuple(jax.device_put(g, target) for g in grids)
                      for grids in parts), rows)
            _C_GATHER_BYTES.inc(moved)
            if sp is not None:
                sp.tags["bytes"] = moved
        return outs

    def _dw_warm_shards(self, cold, block: int, statics: dict) -> None:
        """Compile, on the device of each window of ``cold`` ((key,
        shard's window) pairs), every program a stage of ``statics``
        can run there: a stage over one chunk of each shape class the
        window holds, a block of each visited over a range nothing lies
        in, built and thrown away. A program belongs to one device, the
        shard a one-host panel folds on follows the host it drew and a
        metric's chunks pad to their own classes, so without it the
        first request to fold on a shard, or on a class, compiles
        under that request. The shards do it side by side: the compiler
        works outside the interpreter lock."""
        def warm(window):
            classes = window.chunk_classes()
            kernels.window_series_stage_chunks(
                classes, np.int32(1), np.int32(0), np.int32(0),
                blocks=[(0,)] * len(classes), block=block,
                device=window.device, **statics)
        with concurrent.futures.ThreadPoolExecutor(len(cold)) as pool:
            list(pool.map(warm, [window for _key, window in cold]))
        for key, _window in cold:
            self._dw_shard_warm.put(key, True)

    def _devwindow_groups(self, dw, metric_uid: bytes, cols, exact,
                          group_bys):
        """Filter + group the window's series directory on host UIDs.

        Returns ({group_key_tuple: [sid]}, {sid: named_tags}, the
        groups as the answer takes them (_GridGroups, their labels kept
        from the first answer on), whether the plan cache held them);
        cached per (window instance, metric, filter) until the
        directory grows.
        ``dw`` is the SAME window object ``cols`` came from (passed by
        the caller, not re-read from self.tsdb — a swap between capture
        and here must not cache the old window's plan under the new
        window's instance_id)."""
        fkey = (dw.instance_id, metric_uid,
                _filter_key(exact, group_bys))
        cache = self._dw_plan_cache
        hit = cache.get(fkey)
        if hit is not None and hit[0] == cols.generation:
            return hit[1], hit[2], hit[3], True
        groups, named = self._series_groups(cols.series_keys, exact,
                                            group_bys)
        grid = _GridGroups(groups)
        cache.put(fkey, (cols.generation, groups, named, grid))
        return groups, named, grid, False

    # -- fused decode-aggregate path (TSST4 blocks) --------------------

    @staticmethod
    def _series_selector(exact, group_bys):
        """The ONE tag-filter/group-by predicate behind the resident-
        window and fused plans (they must answer identically, so the
        semantics live in one function): series_key -> group key tuple
        when the series matches, None when filtered out. The fused
        path pushes this down into compress/fused.gather, where it
        runs against block keys BEFORE payload decode."""
        group_by_keys = sorted(k for k, _ in group_bys)
        want = dict(exact)
        gb = {k: (set(v) if v else None) for k, v in group_bys}

        def selector(skey: bytes):
            tag_uids = codec.series_tag_uids(skey)
            for k, v in want.items():
                if tag_uids.get(k) != v:
                    return None
            for k, allowed in gb.items():
                v = tag_uids.get(k)
                if v is None or (allowed is not None
                                 and v not in allowed):
                    return None
            return tuple(tag_uids.get(k, b"") for k in group_by_keys)

        return selector

    def _named_tags(self, skey: bytes) -> dict[str, str]:
        return {self.tsdb.tagk.get_name(k): self.tsdb.tagv.get_name(v)
                for k, v in codec.series_tag_uids(skey).items()}

    def _series_groups(self, series_keys, exact, group_bys):
        """Filter + group a series-key directory on host UIDs via
        ``_series_selector``. sid = position in ``series_keys``.
        Returns ({group_key_tuple: [sid]}, {sid: named_tags})."""
        selector = self._series_selector(exact, group_bys)
        groups: dict[tuple, list[int]] = {}
        named: dict[int, dict[str, str]] = {}
        for sid, skey in enumerate(series_keys):
            g = selector(skey)
            if g is None:
                continue
            groups.setdefault(g, []).append(sid)
            named[sid] = self._named_tags(skey)
        return groups, named

    def _run_fused_blocks(self, spec: QuerySpec, start: int, end: int,
                          agg) -> list[QueryResult] | None:
        """Serve a downsampled query straight from TSST4 compressed
        blocks: one fused decode-plus-aggregate XLA program produces
        the per-(series, bucket) stage grids (the decoded columns are
        never materialized on host), then the SAME apply kernels the
        device-resident window uses finish grouping/percentiles.
        Exact or None (the fall-back contract): any memtable-resident
        data in range, non-v4 generation, non-TSF32 block, overlay
        risk, or int32 overflow declines to the scan path."""
        tsdb = self.tsdb
        cfg = tsdb.config
        if (self.backend == "cpu"
                or not spec.downsample
                or agg.kind not in ("moment", "percentile")
                or Aggregators.get(spec.downsample[1]).kind != "moment"
                or not cfg.sstable_fused_agg):
            return None
        store = tsdb.store
        if getattr(store, "encoded_range", None) is None \
                or getattr(store, "chunk_state", None) is None:
            return None
        interval, dsagg = spec.downsample
        imax = 2**31 - 1
        if start < 0 or end > 0xFFFFFFFF \
                or end - start > imax - 4 * MAX_TIMESPAN:
            return None
        qbase = start - start % interval
        if end - qbase > imax:
            return None
        from opentsdb_tpu.core.errors import NoSuchUniqueName
        try:
            metric_uid = tsdb.metrics.get_id(spec.metric)
            exact, group_bys = self._tag_filters(spec.tags)
        except NoSuchUniqueName:
            return None  # scan path raises the canonical error
        b_lo = codec.base_time(start)
        b_hi = min(codec.base_time(end), 0xFFFFFFFF)
        _C_FUSED_ATTEMPT.inc()
        # Memtable-resident (dirty) data in range: decline — a frozen
        # answer must equal the scan bit-for-bit, and overlaying live
        # rows is the scan path's job.
        seqs, floors, stamps, dirty = store.chunk_state(
            tsdb.table, b_lo, b_hi + MAX_TIMESPAN)
        if dirty:
            _count_decline("dirty")
            return None
        with _M_FUSED.time():
            res = self._run_fused_inner(
                spec, start, end, agg, metric_uid, exact, group_bys,
                interval, dsagg, qbase, b_lo, b_hi)
        if res is not None:
            _C_FUSED_SERVED.inc()
        return res

    def _run_fused_inner(self, spec, start, end, agg, metric_uid,
                         exact, group_bys, interval, dsagg, qbase,
                         b_lo, b_hi):
        """The fused plan past its gates, as five spans under
        planner.pick (README, "Observability"): fused.gather (which
        blocks, their records, the groups), fused.dispatch (the
        uploads and the calls of the stage and apply programs),
        fused.wait (traced requests only, as aggregate.wait),
        fused.fetch, fused.results."""
        from opentsdb_tpu.compress import fused as _fused
        tsdb = self.tsdb
        rate_kw = self._rate_kw(spec)
        fk = _filter_key(exact, group_bys)
        # The tag filter is part of the stage's identity now that it's
        # pushed into the gather (filtered-out series never reach the
        # stage grid) — leaving it out would serve one filter's grid
        # under another's key.
        skey_cache = (metric_uid, b_lo, b_hi, interval, dsagg, start,
                      end, fk, tuple(sorted(rate_kw.items())))
        hit = self._fused_stage_cache.get(skey_cache)
        if hit is not None:
            gens_hit, src_keys, epoch, stage, groups = hit
            # Validate against the CURRENT generation set: gens_hit
            # holds the SSTable objects the cached stage was computed
            # from (object identity — the entry pins them, so id
            # recycling cannot alias a dropped generation). Any
            # checkpoint/compaction swap mismatches and rebuilds.
            spans = tsdb.store.encoded_range(
                tsdb.table, metric_uid + b_lo.to_bytes(4, "big"),
                metric_uid + min(b_hi + MAX_TIMESPAN,
                                 0xFFFFFFFF).to_bytes(4, "big"))
            if spans is None or \
                    len(spans) != len(gens_hit) or \
                    any(g is not h for (g, _, _), h
                        in zip(spans, gens_hit)):
                hit = None
                self._fused_stage_cache.pop(skey_cache)
        src = None
        if hit is None:
            with obs_trace.span("fused.gather") as sp:
                memo = self._fused_sel_memo.get((metric_uid, fk))
                if memo is None:
                    memo = {}
                    self._fused_sel_memo.put((metric_uid, fk), memo)
                try:
                    src = _fused.gather(
                        tsdb.store, tsdb.table, metric_uid, b_lo, b_hi,
                        selector=self._series_selector(exact, group_bys),
                        series_keys=self._series_hint(
                            metric_uid, exact, group_bys).get(
                                "series_keys"),
                        sel_memo=memo)
                except _fused.Decline as d:
                    _count_decline(d.reason)
                    return None
                if sp is not None:
                    dc = self._devcache
                    sp.tags.update(
                        blocks=len(src.blocks), points=src.npoints,
                        matched=src.matched,
                        series=len(src.series_keys),
                        payload_bytes=src.payload_bytes(),
                        cached=dc.held(src) if dc is not None else 0)
            if src.npoints == 0:
                return []
            _C_FUSED_POINTS.inc(src.npoints)
            _C_FUSED_MATCHED.inc(src.matched)
            _C_FUSED_PAYLOAD.inc(src.payload_bytes())
            epoch = src.epoch
            src_keys = src.series_keys
            groups = src.groups
        if not groups:
            return []
        S_pad = _pad_size(len(src_keys))
        imin, imax = -(2**31), 2**31 - 1
        if not imin <= qbase - epoch <= imax:
            _count_decline("int32-span")
            return None
        num_buckets = _pad_size(int((end - qbase) // interval + 1))
        if S_pad * num_buckets >= 2**31:
            _count_decline("grid-too-large")
            return None
        grid = self._fused_plan_cache.get((metric_uid, fk))
        if grid is None or grid.series_keys != src_keys:
            # New to this executor, or the store has gained or lost a
            # series of the range since: the groups' sids are positions
            # in the gather's directory, so the kept labels go with it.
            grid = _GridGroups(groups, src_keys)
            self._fused_plan_cache.put((metric_uid, fk), grid)
        gkeys = grid.gkeys
        G = _pad_size(len(gkeys))
        ngroups = 1 if len(gkeys) == 1 else G
        b_live = int((end - qbase) // interval + 1)
        g_out = min(ngroups, _pad64(len(gkeys)))
        b_out = min(num_buckets, _pad64(b_live))
        with obs_trace.span("fused.dispatch") as sp:
            if src is not None:
                try:
                    stage, leg = self._fused_stage(
                        src, S_pad, num_buckets, interval, dsagg,
                        rate_kw,
                        np.int32(min(max(start - epoch, imin), imax)),
                        np.int32(min(max(end - epoch, imin), imax)),
                        np.int32(qbase - epoch))
                except _fused.Decline as d:
                    _count_decline(d.reason)
                    return None
                # Key the entry on the SNAPSHOT the stage was actually
                # computed from (src.spans — not a fresh encoded_range,
                # which a checkpoint racing this query could have moved
                # past the gathered data). The held objects both pin
                # against id reuse and make hit-validation pure identity.
                self._fused_stage_cache.put(
                    skey_cache,
                    (tuple(g for g, _, _ in src.spans),
                     src_keys, epoch, stage, groups))
            else:
                leg = "cached"
            sv, sm, filled, in_range, presence_dev = stage[:5]
            include = np.zeros(S_pad, bool)
            gmap = np.full(S_pad, G - 1, np.int32)
            for gi, gkey in enumerate(gkeys):
                sids = groups[gkey]
                include[sids] = True
                gmap[sids] = gi
            shrink = dict(g_out=g_out, b_out=b_out,
                          wire_bf16=bool(tsdb.config.wire_bf16))
            if agg.kind == "percentile":
                gv, gm = kernels.window_quantile_apply(
                    sm, filled, in_range, include, gmap,
                    np.array([agg.quantile], np.float32),
                    num_groups=ngroups, **shrink)
            else:
                gv, gm = kernels.window_moment_apply(
                    sv, sm, filled, in_range, include, gmap,
                    num_groups=ngroups, agg_group=spec.aggregator,
                    **shrink)
            if sp is not None:
                sp.tags["leg"] = leg
        if obs_trace.current_span() is not None:
            # Traced requests only, as aggregate.wait: untraced the
            # fetch below blocks as it always did.
            with obs_trace.span("fused.wait"):
                jax.block_until_ready((gv, gm))
        with obs_trace.span("fused.fetch") as sp:
            if stage[5] is None:
                gv, gm, stage[5] = jax.device_get((gv, gm, presence_dev))
            else:
                gv, gm = jax.device_get((gv, gm))
            if sp is not None:
                sp.tags["bytes"] = int(gv.nbytes + gm.nbytes)
        with obs_trace.span("fused.results") as sp:
            named = self._fused_named
            if len(named) > 1 << 20:
                named.clear()

            def tags_of(sid: int) -> dict[str, str]:
                sk = src_keys[sid]
                tags = named.get(sk)
                if tags is None:
                    tags = named[sk] = self._named_tags(sk)
                return tags

            results = _grid_results(spec.metric, grid, tags_of, stage[5],
                                    gv, gm, b_out, interval, qbase)
            if sp is not None:
                sp.tags["results"] = len(results)
        return results

    def _fused_stage(self, src, S_pad, num_buckets, interval, dsagg,
                     rate_kw, lo32, hi32, shift32):
        """Dispatch the window stage of one gather; returns (the stage
        contract as a list with a slot for the fetched presence, the
        leg that ran). On one device the gather's blocks are decoded
        into the block cache's slabs (misses only) and the stage reads
        them: per matched point where the selector keeps under half of
        the points of the blocks it touches (``sel``), else per whole
        block (``rows``); without the cache, or for a gather its slabs
        cannot hold, the plan declines (``cache-off``, ``oversize``)
        and the raw plan serves. Across a mesh the byte-stream leg
        decodes and stages in one program (``mesh``)."""
        from opentsdb_tpu.compress import fused as _fused
        from opentsdb_tpu.compress import kernels as _ckernels
        statics = dict(
            num_series=S_pad, num_buckets=num_buckets,
            interval=interval, agg_down=dsagg, rate=rate_kw["rate"],
            counter=rate_kw["counter"],
            drop_resets=rate_kw["drop_resets"])
        scalars = (lo32, hi32, shift32,
                   np.float32(rate_kw["counter_max"]),
                   np.float32(rate_kw["reset_value"]))

        def counted(out, slots):
            *grids, handed = out
            _stage_handed(handed, slots)
            return grids + [None]

        if self.mesh is None:
            dc = self._devcache
            if dc is None:
                raise _fused.Decline("cache-off")
            selective = 2 * src.matched <= src.npoints

            def run(qd, vals, slots):
                inputs = (dc.point_inputs if selective
                          else dc.record_inputs)(src, slots, S_pad)
                # The stream: a matched point each, or every point of
                # the rows gathered.
                return counted(
                    (_ckernels.slab_stage_sel if selective
                     else _ckernels.slab_stage_rows)(
                        qd, vals, *inputs, *scalars, **statics),
                    len(inputs[0]) * (1 if selective else dc.P_BLK))

            stage = dc.stage(src, run)
            if stage is None:
                raise _fused.Decline("oversize")
            return stage, "sel" if selective else "rows"
        # The plane's pjit-preferred leg: the point stream (whole
        # compressed blocks) shards over the mesh, payloads and the
        # [S, B] outputs replicate (compress/kernels.py
        # FUSED_STAGE_PLAN). Shapes that don't divide the mesh run the
        # single-device compile — counted (mesh-indivisible) but still
        # served fused, never a fallback to the scan.
        ps = src.point_stream()
        npoints = len(ps.valid)
        _C_FUSED_UPLOADED.inc(
            14 * npoints + len(ps.ts_pay) + len(ps.v_pay))
        P_pad = _pad_fine(npoints)

        def pad(a, dtype, fill=0):
            out = np.full(P_pad, fill, dtype)
            out[:len(a)] = a
            return out

        def padbuf(a):
            # Payload bytes pad pow2: decode compute is per-POINT,
            # byte padding costs only upload, and one compile class
            # per octave keeps shifted windows from recompiling on
            # byte-length wobble.
            n = max(len(a), 1)
            out = np.zeros(1 << (n - 1).bit_length(), np.uint8)
            out[:len(a)] = a
            return out

        args = (pad(ps.ts_nb, np.int32), padbuf(ps.ts_pay),
                pad(ps.v_nb, np.int32), padbuf(ps.v_pay),
                pad(ps.first_idx, np.int32),
                pad(ps.blk_first, np.int32),
                pad(ps.rel_base_pt, np.int32),
                pad(np.minimum(ps.sid_pt, S_pad - 1), np.int32),
                pad(ps.valid, bool, False))
        if P_pad % int(self.mesh.devices.size) == 0:
            fused_fn = _ckernels.fused_block_stage_mesh(
                self.mesh, vkind=src.kind, **statics)
            return counted(fused_fn(*args, *scalars), P_pad), "mesh"
        _count_decline("mesh-indivisible")
        out = _ckernels.fused_block_stage(
            *args, *scalars[:3], **statics, vkind=src.kind,
            counter_max=rate_kw["counter_max"],
            reset_value=rate_kw["reset_value"])
        return counted(out, P_pad), "bytes"

    # -- CPU oracle backend -------------------------------------------

    def _run_cpu(self, spec: QuerySpec, spans: list[_Span], start: int):
        series = []
        for sp in spans:
            ts, vals = sp.timestamps, sp.values
            if spec.downsample:
                interval, dsagg = spec.downsample
                ts, vals = oracle.downsample(ts, vals, interval, dsagg,
                                             mode="aligned",
                                             bucket_ts="start")
            if spec.rate:
                ts, vals = oracle.rate(
                    ts, vals,
                    counter_max=spec.counter_max if spec.counter else None,
                    reset_value=spec.reset_value)
            if len(ts):
                series.append((ts, vals))
        if not series:
            return (np.empty(0, np.int64), np.empty(0, np.float64))
        interp = self._interp(spec)
        return oracle.group_aggregate(series, spec.aggregator,
                                      interp=interp)

    @staticmethod
    def _interp(spec: QuerySpec) -> str:
        """Group-stage gap policy: the zimsum/mimmin/mimmax family never
        interpolates; rates hold the last value; everything else lerps
        (reference SGIterator semantics, SpanGroup.java:702-784)."""
        if not Aggregators.get(spec.aggregator).interpolates:
            return "none"
        return "step" if spec.rate else "lerp"

    # -- TPU kernel backend -------------------------------------------

    def _run_tpu(self, spec: QuerySpec, spans: list[_Span], start: int):
        """One group of an un-downsampled request: optional rate, then
        union-grid interpolation, all on device. (A downsampled one is
        fused, rate included: _execute_groups.)"""
        series = [(sp.timestamps, sp.values) for sp in spans]
        if spec.rate:
            series = self._tpu_rate(series, spec)
            series = [s for s in series if len(s[0])]
        if not series:
            return (np.empty(0, np.int64), np.empty(0, np.float64))
        S = len(series)
        T = _pad_size(max(len(s[0]) for s in series))
        base = min(int(s[0][0]) for s in series)
        ts_pad = np.zeros((S, T), np.int32)
        val_pad = np.zeros((S, T), np.float32)
        counts = np.zeros(S, np.int32)
        for i, (ts, vals) in enumerate(series):
            n = len(ts)
            ts_pad[i, :n] = ts - base
            val_pad[i, :n] = vals
            counts[i] = n
        interp = self._interp(spec)
        if Aggregators.get(spec.aggregator).kind == "percentile":
            grid, out, gmask = self._tpu_quantile_grid(
                ts_pad, val_pad, counts, spec, interp)
        else:
            grid, out, gmask = kernels.group_interpolate(
                ts_pad, val_pad, counts, agg=spec.aggregator,
                interp=interp)
        gmask = np.asarray(gmask)
        return (np.asarray(grid)[gmask].astype(np.int64) + base,
                np.asarray(out)[gmask].astype(np.float64))

    def _tpu_quantile_grid(self, ts_pad, val_pad, counts, spec, interp):
        """Union-grid percentile: build the grid once, compute per-series
        contributions with interp, then quantile across series."""
        grid, gmask = kernels.union_grid(ts_pad, counts)
        q = Aggregators.get(spec.aggregator).quantile
        contrib, cmask = kernels.series_contributions(
            ts_pad, val_pad, counts, np.asarray(grid), interp=interp)
        out = kernels.masked_quantile_axis0(contrib, cmask,
                                            np.array([q], np.float32))[0]
        return grid, out, gmask

    def _tpu_rate(self, series, spec: QuerySpec):
        """Rate each series on device via the flat kernel."""
        if not series:
            return series
        ts = np.concatenate([s[0] for s in series]).astype(np.int64)
        base = int(ts.min()) if len(ts) else 0
        flat_ts = (ts - base).astype(np.int32)
        vals = np.concatenate([s[1] for s in series]).astype(np.float32)
        sid = np.concatenate([
            np.full(len(s[0]), i, np.int32)
            for i, s in enumerate(series)])
        valid = np.ones(len(flat_ts), bool)
        rates, ok = kernels.flat_rate(
            flat_ts, vals, sid, valid,
            counter_max=spec.counter_max,
            reset_value=spec.reset_value or 0.0,
            counter=spec.counter,
            drop_resets=spec.reset_value is not None)
        rates, ok = np.asarray(rates), np.asarray(ok)
        out = []
        for i, (sts, _) in enumerate(series):
            m = (sid == i) & ok
            out.append((ts[m], rates[m].astype(np.float64)))
        return out

    def _rate_kw(self, spec: QuerySpec) -> dict:
        """Static+traced rate args threaded into the fused kernels."""
        return dict(
            rate=spec.rate,
            counter_max=spec.counter_max if spec.counter else 0.0,
            reset_value=spec.reset_value or 0.0,
            counter=spec.counter,
            drop_resets=spec.reset_value is not None)

    def _tpu_downsample_group(self, spec: QuerySpec, scan: _Scan,
                              start: int, end: int):
        """The fused fast path for a scan of one group: flat downsample
        [+ rate] + cross-series group, one kernel call."""
        interval, dsagg = spec.downsample
        qbase = start - start % interval
        # Pad the static kernel shapes to power-of-two buckets: padded
        # series/buckets hold no points, contribute nothing, and are
        # trimmed by group_mask — but the jit cache stops keying on the
        # exact (S, B) of every distinct query.
        num_buckets = _pad_size(int((end - qbase) // interval + 1))
        agg = Aggregators.get(spec.aggregator)
        if self.mesh is not None and agg.kind in ("moment", "percentile"):
            (spans,) = scan.spans().values()
            sharded = self._tpu_downsample_sharded(
                spec, spans, qbase, interval, dsagg, num_buckets)
            if sharded is not None:
                return sharded
        with obs_trace.span("aggregate.pack") as sp:
            rel, vals, sid, valid = scan.stream(qbase, pad=True)
            if sp is not None:
                sp.tags.update(series=len(scan.keys), slots=len(rel))
        with obs_trace.span("aggregate.dispatch"):
            out = kernels.downsample_group(
                rel, vals, sid, valid,
                num_series=_pad_size(len(scan.keys)),
                num_buckets=num_buckets, interval=interval,
                agg_down=dsagg,
                agg_group=(spec.aggregator if agg.kind == "moment"
                           else "count"),
                **self._rate_kw(spec))
            _stage_handed(out["handed"], len(rel))
            gmask, values = out["group_mask"], out["group_values"]
            if agg.kind == "percentile":
                # series_values/series_mask are the post-rate per-bucket
                # signal when spec.rate; rates step-hold, plain values
                # lerp.
                fill = kernels.step_fill if spec.rate else kernels.gap_fill
                filled, in_range = fill(
                    out["series_values"], out["series_mask"],
                    int(num_buckets))
                values = kernels.masked_quantile_axis0(
                    filled, in_range,
                    np.array([agg.quantile], np.float32))[0]
        gmask, values = self._aggregate_fetch(gmask, values)
        with obs_trace.span("aggregate.results"):
            # Epoch-aligned bucket-start timestamps (module docstring).
            grid_ts = (np.flatnonzero(gmask).astype(np.int64) * interval
                       + qbase)
            return grid_ts, values[gmask].astype(np.float64)

    @staticmethod
    def _aggregate_fetch(gmask, values):
        """The device's answer brought to the host, as the two spans
        after aggregate.dispatch. The wait is issued ONLY in a traced
        request: the kernels above are dispatches (JAX returns before
        the device finishes), so without it the h2d copy and the
        device's time would all land in the fetch. Untraced the path
        makes no such call."""
        if obs_trace.current_span() is not None:
            with obs_trace.span("aggregate.wait"):
                jax.block_until_ready((gmask, values))
        with obs_trace.span("aggregate.fetch") as sp:
            gmask, values = jax.device_get((gmask, values))
            if sp is not None:
                sp.tags["bytes"] = int(gmask.nbytes + values.nbytes)
        return gmask, values

    def _tpu_downsample_sharded(self, spec: QuerySpec, spans: list[_Span],
                                qbase: int, interval: int, dsagg: str,
                                num_buckets: int):
        """Distribute one group's fused downsample [+ rate] over self.mesh.

        Series-parallel when the group has >= one series per chip
        (zero-comm local downsample+rate, psum moment fan-in — or an
        all_gather of per-bucket contributions for percentile group
        aggregation, which doesn't decompose into moments); time-parallel
        for long ranges with few series (bucket-aligned tiles, edge-
        summary carries for lerp, step-hold AND rate predecessors).
        Returns (grid_ts, values) or None when neither layout pays (the
        caller falls back to single-device).
        """
        from opentsdb_tpu.parallel.mesh import TIME_AXIS, Mesh
        from opentsdb_tpu.parallel.sharded import (
            pack_shards,
            sharded_downsample_group,
            sharded_downsample_quantile,
        )
        from opentsdb_tpu.parallel.timeshard import (
            pack_time_shards,
            timeshard_downsample_group,
        )

        agg = Aggregators.get(spec.aggregator)
        rate_kw = self._rate_kw(spec)
        D = int(self.mesh.devices.size)
        if len(spans) >= D:
            series = [((sp.timestamps - qbase).astype(np.int64),
                       sp.values) for sp in spans]
            ts, vals, sid, valid, sps = pack_shards(series, D)
            if agg.kind == "percentile":
                gv, gm = sharded_downsample_quantile(
                    ts, vals, sid, valid,
                    np.array([agg.quantile], np.float32), mesh=self.mesh,
                    series_per_shard=_pad_size(sps),
                    num_buckets=num_buckets, interval=interval,
                    agg_down=dsagg, **rate_kw)
                gv = gv[0]
            else:
                gv, gm = sharded_downsample_group(
                    ts, vals, sid, valid, mesh=self.mesh,
                    series_per_shard=_pad_size(sps),
                    num_buckets=num_buckets,
                    interval=interval, agg_down=dsagg,
                    agg_group=spec.aggregator, **rate_kw)
        elif num_buckets >= 4 * D:
            bps = -(-num_buckets // D)
            rel, vals, sid, valid = self._flatten_spans(spans, qbase)
            tsh = pack_time_shards(rel[valid], vals[valid], sid[valid], D,
                                   interval, bps)
            tmesh = Mesh(self.mesh.devices.reshape(-1), (TIME_AXIS,))
            gv, gm = timeshard_downsample_group(
                *tsh, mesh=tmesh, num_series=_pad_size(len(spans)),
                buckets_per_shard=bps, interval=interval, agg_down=dsagg,
                agg_group=(spec.aggregator if agg.kind == "moment"
                           else "count"),
                quantile=(agg.quantile if agg.kind == "percentile"
                          else None), **rate_kw)
        else:
            return None
        gm = np.asarray(gm)
        grid_ts = np.flatnonzero(gm).astype(np.int64) * interval + qbase
        return grid_ts, np.asarray(gv)[gm].astype(np.float64)

    @staticmethod
    def _flatten_spans(spans: list[_Span], qbase: int):
        """Spans -> one flat unpadded (rel_ts, vals, sid, valid) point
        stream, sid = position in ``spans``: what the mesh packers of
        one group take."""
        return _Scan.of_spans({(): spans}).stream(qbase)

    def _run_tpu_multigroup(self, spec: QuerySpec, scan: _Scan,
                            gkeys: list[tuple], start: int, end: int):
        """All group-by buckets in one fused kernel call.

        The scan's one point stream with a series->group map;
        downsample_multigroup runs the per-series and per-group
        reductions for all G groups at once. Returns
        [(grid_ts, values)] aligned with ``gkeys``.
        """
        interval, dsagg = spec.downsample
        qbase = start - start % interval
        num_buckets = _pad_size(int((end - qbase) // interval + 1))

        G = _pad_size(len(gkeys))
        agg = Aggregators.get(spec.aggregator)
        D = int(self.mesh.devices.size) if self.mesh is not None else 0
        if D and len(scan.keys) >= D:
            spans = scan.spans()
            gv, gm = self._multigroup_sharded(
                spec, [sp for k in gkeys for sp in spans[k]],
                [gi for gi, k in enumerate(gkeys) for _ in spans[k]],
                G, qbase, interval, dsagg, num_buckets, D)
        else:
            with obs_trace.span("aggregate.pack") as sp:
                rel, vals, sid, valid = scan.stream(qbase, pad=True)
                # Shapes padded to power-of-two buckets (see
                # _tpu_downsample_group). Padded series are assigned
                # group G-1 (possibly a REAL group when the count is
                # already a power of two) — safe solely because padded
                # series carry no points, so they contribute nothing
                # wherever they land.
                S = _pad_size(len(scan.keys))
                group_of = [0] * len(scan.keys)
                for gi, k in enumerate(gkeys):
                    for i in scan.groups[k]:
                        group_of[i] = gi
                gmap = np.full(S, G - 1, np.int32)
                gmap[:len(group_of)] = group_of
                if sp is not None:
                    sp.tags.update(series=len(scan.keys), slots=len(rel))
            with obs_trace.span("aggregate.dispatch"):
                if agg.kind == "percentile":
                    out = kernels.downsample_multigroup_quantile(
                        rel, vals, sid, valid, gmap,
                        np.array([agg.quantile], np.float32),
                        num_series=S, num_groups=G,
                        num_buckets=num_buckets,
                        interval=interval, agg_down=dsagg,
                        **self._rate_kw(spec))
                else:
                    out = kernels.downsample_multigroup(
                        rel, vals, sid, valid, gmap,
                        num_series=S, num_groups=G,
                        num_buckets=num_buckets, interval=interval,
                        agg_down=dsagg, agg_group=spec.aggregator,
                        **self._rate_kw(spec))
                _stage_handed(out["handed"], len(rel))
            gm, gv = self._aggregate_fetch(out["group_mask"],
                                           out["group_values"])
        with obs_trace.span("aggregate.results"):
            results = []
            for gi in range(len(gkeys)):
                mask = gm[gi]
                grid_ts = (np.flatnonzero(mask).astype(np.int64) * interval
                           + qbase)
                results.append((grid_ts, gv[gi][mask].astype(np.float64)))
        return results

    def _multigroup_sharded(self, spec: QuerySpec, all_spans: list[_Span],
                            group_of_sid: list[int], G: int, qbase: int,
                            interval: int, dsagg: str, num_buckets: int,
                            D: int):
        """Wide group-by over the mesh: series round-robin across chips
        with a per-shard group map; psum per-(group, bucket) fan-in for
        moments, all_gather + grouped radix select for percentiles.
        Fixes the single-device multigroup/mesh perf inversion (round-1
        advisor finding)."""
        from opentsdb_tpu.parallel.sharded import (
            pack_shards,
            shard_placement,
            sharded_downsample_multigroup,
            sharded_downsample_multigroup_quantile,
        )
        series = [((sp.timestamps - qbase).astype(np.int64), sp.values)
                  for sp in all_spans]
        ts, vals, sid, valid, sps = pack_shards(series, D)
        sps_pad = _pad_size(sps)
        # Group map laid out by the packing's own placement. Padded local
        # series map to group G-1 — safe, they carry no points.
        gmap = np.full((D, sps_pad), G - 1, np.int32)
        for (d, local), g in zip(shard_placement(len(series), D),
                                 group_of_sid):
            gmap[d, local] = g
        agg = Aggregators.get(spec.aggregator)
        if agg.kind == "percentile":
            gv, gm = sharded_downsample_multigroup_quantile(
                ts, vals, sid, valid, gmap,
                np.array([agg.quantile], np.float32), mesh=self.mesh,
                series_per_shard=sps_pad, num_groups=G,
                num_buckets=num_buckets, interval=interval,
                agg_down=dsagg, **self._rate_kw(spec))
        else:
            gv, gm = sharded_downsample_multigroup(
                ts, vals, sid, valid, gmap, mesh=self.mesh,
                series_per_shard=sps_pad, num_groups=G,
                num_buckets=num_buckets, interval=interval,
                agg_down=dsagg, agg_group=spec.aggregator,
                **self._rate_kw(spec))
        return np.asarray(gv), np.asarray(gm)

    # ------------------------------------------------------------------
    # Streaming-sketch queries (no storage rescan)
    # ------------------------------------------------------------------

    def _sketch_series(self, metric: str, tags: dict[str, str],
                       ) -> list[bytes]:
        """Series keys with sketch state matching metric + tag filter —
        selected from the sketch slot directory, not a storage scan. The
        same UID regexp as the scan path, minus the base-time bytes."""
        metric_uid = self.tsdb.metrics.get_id(metric)
        exact, group_bys = [], []
        for name, value in tags.items():
            k = self.tsdb.tagk.get_id(name)
            if value == "*":
                group_bys.append((k, None))
            elif "|" in value:
                group_bys.append(
                    (k, [self.tsdb.tagv.get_id(v)
                         for v in value.split("|")]))
            else:
                exact.append((k, self.tsdb.tagv.get_id(value)))
        regexp = self._build_regexp(exact, group_bys, prefix=UID_WIDTH)
        pattern = re.compile(regexp, re.S) if regexp else None
        return [k for k in self.tsdb.sketches.series_keys()
                if k.startswith(metric_uid)
                and (pattern is None or pattern.match(k))]

    def sketch_quantiles(self, metric: str, tags: dict[str, str],
                         qs: list[float], start: int | None = None,
                         end: int | None = None,
                         max_error: float | None = None) -> dict:
        """Quantiles of the matching series' merged value distribution.

        Without a range: the streaming path — merged per-series
        t-digests folded at ingest (the Histogram.java replacement),
        covering each series' full history, no storage rescan.

        With [start, end]: answered from the rollup tier's per-window
        digest columns — O(windows) digest merges for the covered
        windows plus a raw fold over the partial edges and any dirty
        windows — instead of re-folding every raw value per request.
        When the tier can't serve the range, falls back to an EXACT
        raw-scan quantile (slower, never wrong)."""
        if start is not None or end is not None:
            if start is None or end is None or end <= start:
                raise BadRequestError(
                    "sketch range needs both start and end (end > start)")
            return self._sketch_quantiles_range(metric, tags, qs,
                                                start, end, max_error)
        sk = self.tsdb.sketches
        if sk is None:
            raise BadRequestError(
                "streaming sketches are disabled (enable_sketches)")
        keys = self._sketch_series(metric, tags)
        out = sk.quantile(keys, np.asarray(qs, np.float32))
        if out is None:
            raise BadRequestError(
                f"no sketch state for metric {metric} with those tags")
        return {"metric": metric, "series": len(keys),
                "quantiles": {f"{q:g}": float(v)
                              for q, v in zip(qs, out)}}

    def _sketch_quantiles_range(self, metric: str, tags: dict[str, str],
                                qs: list[float], start: int,
                                end: int,
                                max_error: float | None = None) -> dict:
        from opentsdb_tpu.rollup import planner as rplanner
        from opentsdb_tpu.rollup import summary as rsummary
        from opentsdb_tpu.rollup.tier import res_label
        from opentsdb_tpu.sketch import bounds as _sbounds
        from opentsdb_tpu.sketch.moment import MomentSketch

        def exact_raw() -> dict:
            # Exact raw fallback: pool every in-range value.
            spec = QuerySpec(metric, tags)
            groups = self._find_spans(spec, start, end)
            vals = [sp.values for spans in groups.values()
                    for sp in spans]
            if not vals:
                raise BadRequestError(
                    f"no data for metric {metric} in range")
            pool = np.concatenate(vals)
            # float32 like the digests quantize, so the two paths
            # agree within sketch tolerance, not a dtype offset.
            est = np.quantile(pool.astype(np.float32).astype(np.float64),
                              np.clip(qs, 0.0, 1.0))
            return {"metric": metric, "series": len(vals),
                    "rollup": "raw",
                    "quantiles": {f"{q:g}": float(v)
                                  for q, v in zip(qs, est)}}

        tier = getattr(self.tsdb, "rollups", None)
        sel = rplanner.sketch_windows(self, tier, metric, tags,
                                      start, end)
        if sel is None:
            return exact_raw()
        res, records, raw_parts, dirty = sel
        digest_k = tier.sketch_kinds(res)[0]
        kind = "tdigest" if digest_k else "moment"
        means: list[np.ndarray] = []
        weights: list[np.ndarray] = []
        msk: MomentSketch | None = None
        vmin, vmax = np.inf, -np.inf
        # Pooled-CDF rank uncertainty: each contributing window
        # digest's heaviest centroid weight (bounds.py
        # cdf_uncertainty_w); raw points contribute zero.
        unc = 0.0
        # Series counted by CONTRIBUTION (digest or raw values), not by
        # which map they appear in: a series whose rollup windows are
        # all dirty contributes only through raw_parts but is still in
        # records, so map-membership tests undercount it.
        contributing: set[bytes] = set()
        for skey, (bases, recs, sketches) in records.items():
            wstats = {int(b): (float(r["min"]), float(r["max"]))
                      for b, r in zip(bases, recs)}
            for wb, blob in sketches:
                if wb in dirty:
                    continue
                m, w, _r, mblob = rsummary.sketch_decode_full(blob)
                got = False
                if kind == "tdigest" and len(m):
                    means.append(m.astype(np.float64))
                    weights.append(w.astype(np.float64))
                    unc += float(np.max(w))
                    got = True
                elif kind == "moment" and mblob is not None:
                    ms = MomentSketch.decode(mblob)
                    msk = ms if msk is None else msk.merge(ms)
                    got = True
                if got:
                    contributing.add(skey)
                    lo, hi = wstats.get(int(wb), (np.inf, -np.inf))
                    vmin, vmax = min(vmin, lo), max(vmax, hi)
        for skey, (ts, vals) in raw_parts.items():
            if len(vals):
                v32 = vals.astype(np.float32).astype(np.float64)
                if kind == "tdigest":
                    means.append(v32)
                    weights.append(np.ones(len(vals)))
                else:
                    add = MomentSketch(
                        msk.k if msk is not None else
                        MomentSketch().k).add(v32)
                    msk = add if msk is None else msk.merge(add)
                contributing.add(skey)
                vmin = min(vmin, float(v32.min()))
                vmax = max(vmax, float(v32.max()))
        if kind == "tdigest" and not means:
            return exact_raw()
        if kind == "moment" and (msk is None or msk.count <= 0):
            return exact_raw()
        # Estimates + per-quantile enclosures (the error contract).
        ests, errs = [], {}
        rel_worst = 0.0
        if kind == "tdigest":
            m = np.concatenate(means)
            w = np.concatenate(weights)
            if len(m) > (1 << 16):
                m, w = rsummary.digest_compress(m, w, 4096)
                # The recompression adds its own within-centroid
                # uncertainty on top of the pooled windows'.
                unc += float(np.max(w))
            for q in qs:
                qb = _sbounds.tdigest_quantile_bound(
                    m, w, q, vmin=vmin, vmax=vmax,
                    cdf_uncertainty_w=unc)
                ests.append(qb.est)
                errs[f"{q:g}"] = qb.error
                rel_worst = max(rel_worst,
                                qb.error / max(abs(qb.est), 1e-12))
        else:
            for q in qs:
                qb = _sbounds.moment_quantile_bound(msk, q)
                ests.append(qb.est)
                errs[f"{q:g}"] = qb.error
                rel_worst = max(rel_worst,
                                qb.error / max(abs(qb.est), 1e-12))
        if max_error is not None and rel_worst > max_error:
            # The caller's budget is tighter than the sketch can
            # promise: serve exact instead (slower, never wrong).
            return exact_raw()
        return {"metric": metric, "series": len(contributing),
                "rollup": res_label(res),
                "quantiles": {f"{q:g}": float(v)
                              for q, v in zip(qs, ests)},
                "approx": {"kind": kind, "error": errs,
                           "rel_error": rel_worst,
                           "res": res_label(res)}}

    def sketch_distinct(self, metric: str, tagk: str,
                        start: int | None = None,
                        end: int | None = None) -> int | None:
        """Distinct-tagv count for a metric's tag key.

        Without a range: streaming estimate from the per-(metric, tagk)
        HLL registers folded at ingest; None when the pair has no
        sketch state (caller falls back to the scan path). All-time.

        With [start, end]: EXACT count over the series with data in
        the range, selected from rollup-record presence (O(windows))
        plus raw stitches — or a raw scan when the tier can't serve."""
        return self.sketch_distinct_with_source(metric, tagk,
                                                start, end)[0]

    def sketch_distinct_with_source(
            self, metric: str, tagk: str, start: int | None = None,
            end: int | None = None) -> tuple[int | None, str]:
        """sketch_distinct() plus the label of what actually answered
        THIS call: "stream" (no range), "rollup" (record presence), or
        "scan" (exact fallback). Returned rather than stashed on the
        executor — /distinct reports the source in its JSON, and a
        shared attribute could carry a concurrent request's label."""
        if start is not None or end is not None:
            if start is None or end is None or end <= start:
                raise BadRequestError(
                    "distinct range needs both start and end")
            return self._sketch_distinct_range(metric, tagk, start, end)
        sk = self.tsdb.sketches
        if sk is None:
            return None, "stream"
        from opentsdb_tpu.core.errors import NoSuchUniqueName
        try:
            return (sk.distinct(self.tsdb.metrics.get_id(metric),
                                self.tsdb.tagk.get_id(tagk)), "stream")
        except NoSuchUniqueName:
            return None, "stream"

    def _sketch_distinct_range(self, metric: str, tagk: str, start: int,
                               end: int) -> tuple[int, str]:
        from opentsdb_tpu.core import codec as _codec
        from opentsdb_tpu.rollup import planner as rplanner

        tagk_uid = self.tsdb.tagk.get_id(tagk)
        tier = getattr(self.tsdb, "rollups", None)
        # Presence-only: record existence at ANY resolution answers
        # "which series had data", so short ranges and digest-free
        # tiers still serve from rollups instead of a full exact scan.
        sel = rplanner.sketch_windows(self, tier, metric, {}, start, end,
                                      presence_only=True)
        if sel is None:
            return (self.distinct_tagv(metric, {}, tagk, start, end,
                                       exact=True), "scan")
        _, records, raw_parts, dirty = sel
        vals: set[bytes] = set()
        for skey, (bases, recs, _sk) in records.items():
            live = bases if not dirty else bases[
                ~np.isin(bases, np.fromiter(dirty, np.int64,
                                            len(dirty)))]
            if len(live):
                v = _codec.series_tag_uids(skey).get(tagk_uid)
                if v is not None:
                    vals.add(v)
        for skey in raw_parts:
            v = _codec.series_tag_uids(skey).get(tagk_uid)
            if v is not None:
                vals.add(v)
        return len(vals), "rollup"

    def sketch_distinct_values(self, metric: str, tags: dict[str, str],
                               start: int, end: int) -> dict:
        """Estimated count of DISTINCT VALUES a metric took over a
        range, from the rollup tier's per-window HLL register columns
        (register max across windows/series) plus a raw fold over
        edge/dirty windows. Exact (set-based) fallback when the tier
        can't serve the range."""
        from opentsdb_tpu.rollup import planner as rplanner
        from opentsdb_tpu.rollup import summary as rsummary
        from opentsdb_tpu.rollup.tier import res_label

        tier = getattr(self.tsdb, "rollups", None)
        # want_hll: only HLL-bearing resolutions may serve a
        # distinct-VALUES estimate — a moment-only rung's cells carry
        # no registers, and folding none of them would return a
        # confident undercount.
        sel = rplanner.sketch_windows(self, tier, metric, tags,
                                      start, end, want_hll=True)
        hll_p = (tier.sketch_kinds(sel[0])[2]
                 if sel is not None else 0)
        if sel is None or not hll_p:
            spec = QuerySpec(metric, tags)
            groups = self._find_spans(spec, start, end)
            uniq: set = set()
            for spans in groups.values():
                for sp in spans:
                    uniq.update(
                        np.unique(sp.values.astype(np.float32)
                                  .view(np.uint32)).tolist())
            return {"metric": metric, "rollup": "raw",
                    "distinct_values": len(uniq)}
        res, records, raw_parts, dirty = sel
        regs = np.zeros(1 << hll_p, np.uint8)
        for skey, (bases, recs, sketches) in records.items():
            for wb, blob in sketches:
                if wb in dirty:
                    continue
                _m, _w, r = rsummary.sketch_decode(blob)
                if r is not None and len(r) == len(regs):
                    np.maximum(regs, r, out=regs)
        for skey, (ts, vals) in raw_parts.items():
            if len(vals):
                rsummary.hll_update(
                    regs, vals.astype(np.float32).view(np.uint32))
        from opentsdb_tpu.sketch.bounds import hll_error
        est = int(round(rsummary.hll_estimate(regs)))
        return {"metric": metric, "rollup": res_label(res),
                "distinct_values": est,
                "approx": {"kind": "hll",
                           "error": hll_error(hll_p, est)}}

    # ------------------------------------------------------------------
    # Cardinality (distinct tag values)
    # ------------------------------------------------------------------

    def distinct_tagv(self, metric: str, tags: dict[str, str],
                      tagk: str, start: int, end: int,
                      exact: bool | None = None) -> int:
        """Count distinct values of ``tagk`` among matching series.

        Uses the HyperLogLog kernel on the TPU backend (suitable for
        massive fan-in), exact set counting on the CPU backend or when
        ``exact`` is forced.
        """
        spec = QuerySpec(metric, {**tags, tagk: "*"})
        groups = self._find_spans(spec, start, end)
        uids = []
        for spans in groups.values():
            for sp in spans:
                v = sp.tags.get(tagk)
                if v is not None:
                    uids.append(int.from_bytes(
                        self.tsdb.tagv.get_id(v), "big"))
        if exact or (exact is None and self.backend == "cpu"):
            return len(set(uids))
        if not uids:
            return 0
        items = np.asarray(uids, np.int32)
        pad = _pad_size(len(items))
        padded = np.zeros(pad, np.int32)
        padded[:len(items)] = items
        valid = np.arange(pad) < len(items)
        regs = sketches.hll_add(sketches.hll_init(), padded, valid)
        return int(round(float(sketches.hll_estimate(regs))))


def _u32(v: int) -> bytes:
    return int(v).to_bytes(4, "big")


def _pad_size(n: int) -> int:
    """Round up to a power of two (min 16) to bound jit recompilations."""
    size = 16
    while size < n:
        size *= 2
    return size


def _pad64(n: int) -> int:
    """Round up to a multiple of 64 (min 64): fetch-slice quantization —
    fine enough to cut padded-transfer waste, coarse enough to bound
    the distinct static shapes the apply kernels compile for."""
    return max((n + 63) // 64 * 64, 64)


def _device_id(device) -> int | None:
    """A span's tag for the device a shard is pinned to (None: the
    default placement)."""
    return None if device is None else int(device.id)


def _dw_chunks(cols) -> list:
    """Every device chunk of a resident window's columns, the sharded
    window's shard by shard (for a span's counts)."""
    shards = getattr(cols, "shards", None)
    if shards is None:
        return cols.chunks
    return [c for sc in shards if sc is not None for c in sc.chunks]


def _dw_fold_extent(cols) -> tuple[int, ...]:
    """DevChunks.fold_extent() of a resident window's columns, summed
    over the sharded window's shards: (blocks picked, blocks in all,
    slots picked, slots in all, chunks hit, fold calls), and after
    them the device programs the stage build issues: the calls, for
    each shard its start and its finish, and the join of a sharded
    window's shards."""
    shards = getattr(cols, "shards", None)
    parts = [cols] if shards is None else list(filter(None, shards))
    sums = tuple(map(sum, zip(*(p.fold_extent() for p in parts))))
    return sums + (sums[5] + 2 * len(parts) + (shards is not None),)


class KeptTags(dict):
    """The tags of a label kept with its plan (``_GridGroups.labels``),
    with the label's ``aggregated`` list and room for the ``text`` an
    encoder of answers made of the two (server/qjson.py fills it on the
    label's first answer, from the event-loop thread alone), so that
    what was formatted lives as long as the label and goes with its
    plan."""

    __slots__ = ("aggregated", "text")

    def __init__(self, tags: dict[str, str],
                 aggregated: list[str]) -> None:
        super().__init__(tags)
        self.aggregated = aggregated
        self.text: str | None = None


class _GridGroups:
    """The groups of a grid plan (resident, fused) as its answer takes
    them, kept with the plan that made the groups: the sorted group
    keys (row ``i`` of the fetched grids is ``gkeys[i]``), the groups'
    members as one flat array of series ids with the groups' offsets
    into it and their sizes, and, from the first answer on, one ``(tags, aggregated)``
    a group over its whole membership (``_grid_results`` builds them).
    ``series_keys`` is the directory the ids are positions in, where
    the plan is told by it (fused); the resident plan is told by its
    window's generation."""

    __slots__ = ("gkeys", "members", "offsets", "sizes", "labels",
                 "series_keys")

    def __init__(self, groups: dict[tuple, list[int]],
                 series_keys: list[bytes] | None = None) -> None:
        self.gkeys = sorted(groups)
        self.sizes = np.array([len(groups[g]) for g in self.gkeys],
                              np.intp)
        self.offsets = np.zeros(len(self.gkeys) + 1, np.intp)
        np.cumsum(self.sizes, out=self.offsets[1:])
        self.members = np.fromiter(
            (sid for g in self.gkeys for sid in groups[g]), np.intp,
            int(self.offsets[-1]))
        self.labels: list[tuple[KeptTags, list[str]]] | None = None
        self.series_keys = series_keys


def _grid_results(metric: str, grid: _GridGroups, tags_of, has_points,
                  gv, gm, b_out: int, interval: int,
                  qbase: int) -> list[QueryResult]:
    """The answer of a grid plan out of its fetched grids, by whole-
    array operations: a result a group with a live member, in the
    order of ``grid.gkeys``.

    ``gv`` / ``gm`` are the fetched ``[g_out, b_out]`` values and their
    bit-packed mask (row ``i``: group ``i``), ``has_points`` the
    presence of every series id (a bool array), ``tags_of(sid)`` a series' named tags.
    A series with no point in range must not shape its group's labels
    nor leave an empty group behind (the scan path never sees it): a
    group with none alive is dropped, a group with all alive takes the
    labels kept with the plan, and a group in between is labelled anew
    over its live members.

    What is handed out is shared and read-only: the tag dicts and the
    aggregated lists between every answer of the plan (nothing under
    opentsdb_tpu/ writes to a QueryResult's fields), the timestamps
    between the results of one answer where their rows' masks are one
    (hosts that report in step), the values as rows or slices of one
    float64 array."""
    members, offsets = grid.members, grid.offsets
    alive = has_points[members]
    nlive = np.add.reduceat(alive, offsets[:-1], dtype=np.intp)
    rows = np.flatnonzero(nlive)
    if not len(rows):
        return []
    whole = (nlive == grid.sizes)[rows]

    def label(gi: int, live_only: bool):
        sids = members[offsets[gi]:offsets[gi + 1]]
        if live_only:
            sids = sids[alive[offsets[gi]:offsets[gi + 1]]]
        return QueryExecutor._group_tags(
            [tags_of(sid) for sid in sids.tolist()])

    kept = grid.labels is not None
    if not kept:
        # The plan's first answer (two at once build the same twice).
        grid.labels = [(KeptTags(tags, aggregated), aggregated)
                       for tags, aggregated in (
                           label(gi, False)
                           for gi in range(len(grid.gkeys)))]
    live = rows.tolist()
    labels = [lab if w else label(gi, True) for gi, w, lab
              in zip(live, whole.tolist(),
                     map(grid.labels.__getitem__, live))]
    n_kept = np.count_nonzero(whole) if kept else 0
    _C_LABELS_KEPT.inc(n_kept)
    _C_LABELS_COMPUTED.inc(len(rows) - n_kept)
    # The live rows: their values, and their masks as [R, b_out] bytes.
    gv = gv[rows]
    bits = np.unpackbits(gm[rows], axis=1, count=b_out)
    if (bits == bits[0]).all():
        cols = np.flatnonzero(bits[0])
        ts = cols.astype(np.int64) * interval + qbase
        ts.flags.writeable = False
        values = gv[:, cols].astype(np.float64)
        values.flags.writeable = False
        stamps = itertools.repeat(ts)
    else:
        r, c = np.nonzero(bits)
        flat_ts = c.astype(np.int64) * interval + qbase
        flat_ts.flags.writeable = False
        flat_vals = gv[r, c].astype(np.float64)
        flat_vals.flags.writeable = False
        ends = np.cumsum(bits.sum(axis=1, dtype=np.intp)).tolist()
        cuts = list(zip([0] + ends[:-1], ends))
        stamps = (flat_ts[lo:hi] for lo, hi in cuts)
        values = (flat_vals[lo:hi] for lo, hi in cuts)
    return [QueryResult(metric, tags, aggregated, ts, v)
            for (tags, aggregated), ts, v in zip(labels, stamps, values)]


def _is_device_oom(e: Exception) -> bool:
    """Device allocation failure (XLA RESOURCE_EXHAUSTED) — the one
    non-contract error the devwindow path converts into a scan-path
    fallback rather than raising."""
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


def _filter_key(exact, group_bys):
    """Canonical hashable form of a UID-level (exact, group_bys) tag
    filter — the shared component of every devwindow cache key (plan,
    mask, quantile stage). One definition so the keys can't
    desynchronize."""
    return (tuple(sorted(exact)),
            tuple(sorted((k, tuple(v) if v else None)
                         for k, v in group_bys)))
