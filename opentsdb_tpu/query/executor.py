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

import itertools
import re
import threading
import weakref
from typing import NamedTuple

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
from opentsdb_tpu.query import grid as qgrid
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.query.fused import FusedPlan
from opentsdb_tpu.query.grid import (QueryResult, _filter_key, _pad_size,
                                     group_tags)
from opentsdb_tpu.query.resident import ResidentPlan
from opentsdb_tpu.storage.sstable import series_hash
from opentsdb_tpu.utils.lru import LRUCache

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
        # Approx-serving rail cache (sketch/serving.py): per-series
        # (bucket_ts, est, lo, hi) rails for CLEAN fully-window-
        # covered percentile ranges, revalidated against the tier's
        # fold/refresh stamps. Cost = cached buckets.
        self._sketch_rail_cache = LRUCache(16, max_cost=1 << 22)
        self.qcache_hits = 0
        self.qcache_misses = 0
        self.qcache_bypasses = 0
        # The plans _run_planned tries, in order, before the raw scan:
        # each one object that owns its caches, counters and spans
        # (the rollup step sits between them, _run_planned).
        self.resident = ResidentPlan(tsdb, self.backend, mesh,
                                     self._tag_filters)
        self.fused = FusedPlan(tsdb, self.backend, mesh,
                               self._tag_filters, self._series_hint)
        self.plans = [self.resident, self.fused]

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
                rel, vals, sid, valid = _Scan.of_spans(
                    {(): spans}).stream(qbase)
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
            tags, aggregated = group_tags(
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
        def tried(plans):
            """(Answer, label) of the first of ``plans`` that serves."""
            for plan in plans:
                results = plan.serve(spec, start, end, agg)
                if results is not None:
                    return results, plan.label
            return None, "raw"

        with obs_trace.span("planner.pick") as sp:
            # What is materialized goes first: the plans that read no
            # storage (the resident window), then the rollup tiers;
            # both beat re-deriving from storage, which the plans left
            # do (fused: exact or None) before the raw scan does.
            planned = None
            results, plan = tried(p for p in self.plans if p.storage_free)
            if results is None:
                planned = self._plan_rollup(spec, start, end,
                                            rollup_only=rollup_only,
                                            meta_out=meta_out)
                if planned is not None:
                    from opentsdb_tpu.rollup.tier import res_label
                    plan = res_label(planned[2])
                elif rollup_only:
                    from opentsdb_tpu.core.errors import OverloadedError
                    raise OverloadedError(
                        "shedding load: this query needs a raw scan "
                        "(no eligible rollup resolution); retry shortly",
                        retry_after=0.5, status=503)
                else:
                    results, plan = tried(p for p in self.plans
                                          if not p.storage_free)
            if sp is not None:
                sp.tags["plan"] = plan
        if results is not None:
            return results, plan, False
        if planned is not None:
            groups, spec2, _res = planned
            with obs_trace.span("aggregate"):
                results = self._execute_groups(
                    spec2, _Scan.of_spans(groups), start, end)
            return results, plan, False
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
                        *group_tags([scan.tags[i]
                                           for i in scan.groups[gkey]]),
                        ts, vals)
                    for gkey, (ts, vals) in zip(gkeys, per_group)]

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
            over_mesh = self._tpu_downsample_sharded(
                spec, spans, qbase, interval, dsagg, num_buckets)
            if over_mesh is not None:
                return over_mesh
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
                **qgrid.rate_kw(spec))
            qgrid._stage_handed(out["handed"], len(rel))
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
        gmask, values = qgrid.fetch("aggregate", gmask, values)
        with obs_trace.span("aggregate.results"):
            # Epoch-aligned bucket-start timestamps (module docstring).
            grid_ts = (np.flatnonzero(gmask).astype(np.int64) * interval
                       + qbase)
            return grid_ts, values[gmask].astype(np.float64)

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
        rate_kw = qgrid.rate_kw(spec)
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
            rel, vals, sid, valid = _Scan.of_spans(
                {(): spans}).stream(qbase)
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
                        **qgrid.rate_kw(spec))
                else:
                    out = kernels.downsample_multigroup(
                        rel, vals, sid, valid, gmap,
                        num_series=S, num_groups=G,
                        num_buckets=num_buckets, interval=interval,
                        agg_down=dsagg, agg_group=spec.aggregator,
                        **qgrid.rate_kw(spec))
                qgrid._stage_handed(out["handed"], len(rel))
            gm, gv = qgrid.fetch("aggregate", out["group_mask"],
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
                agg_down=dsagg, **qgrid.rate_kw(spec))
        else:
            gv, gm = sharded_downsample_multigroup(
                ts, vals, sid, valid, gmap, mesh=self.mesh,
                series_per_shard=sps_pad, num_groups=G,
                num_buckets=num_buckets, interval=interval,
                agg_down=dsagg, agg_group=spec.aggregator,
                **qgrid.rate_kw(spec))
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
        regexp = self._build_regexp(*self._tag_filters(tags),
                                    prefix=UID_WIDTH)
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

