"""Device-resident columnar hot window — queries without host→device upload.

Motivation (not measured on a local chip): the fused query kernels read
points at HBM speed, while moving those points to the device per query
costs a host→device copy of the whole range.
The reference never faces this because its compute sits where its data is
(Java heap over HBase scans); a TPU-native design has to put the data where
the compute is instead. This module keeps the recent ingest window's flat
columns (rel-timestamp, value, series-id) resident in device HBM, appended
as data arrives, so the steady-state dashboard query touches the host only
for the series directory and the tiny [S]-sized group maps.

Design:

- **Per-metric windows.** Each metric holds a host-side series directory
  (series_key -> dense sid, the group-by/tag-filter substrate) and a list
  of immutable device chunks; a query concatenates the chunks ON DEVICE
  (HBM-to-HBM, no transfer) and caches the result until the next flush.
- **Host staging.** ``append`` is O(1) host work (numpy refs into a list);
  chunks upload in ``staging_points``-sized batches, padded to powers of
  two so jit shapes repeat. One upload per ~million points amortizes the
  slow host link at ingest time, once, instead of per query.
- **Zone maps.** A query's device cost follows the slots its fold is
  handed, not the points in its range. Each chunk records, per block of
  ``ZONE_BLOCK`` slots, the least and greatest timestamp and the least
  and greatest series id of the block's valid slots; ``chunk_columns``
  lists the blocks a range can hit, ``DevChunks.narrowed`` those of
  them that can hold a series the request matched, and the chunked
  stage visits only those (see ``DevChunks``).
- **Exactness, not cache-maybe.** The window only serves a query when its
  answer is guaranteed byte-identical to the storage scan path:
  - per-series timestamps must be strictly monotone across appends (the
    overwhelmingly common collector pattern); an out-of-order or rewritten
    timestamp marks the metric dirty and queries fall back to the scan
    path (``dirty_fallbacks`` counts them);
  - evicting old chunks advances ``complete_from`` (the metric's
    *horizon*); queries reaching before it fall back. The budget is
    the chip's, so the victim is always the chunk holding the OLDEST
    data fleet-wide (least ``max_ts``), whatever order it arrived in:
    live ingest is time-major, so there arrival order is age, but a
    boot refill scans metric by metric, and eviction by arrival would
    leave the first metrics scanned with nothing and the last whole.
  - deletes/fsck rewrites call ``invalidate``.
- **Sizing.** A slot is ``SLOT_BYTES`` = 13 B on the device (int32
  time, float32 value, int32 series id, a validity byte) and a chunk's
  columns are padded to a power of two. A chunk is cut when the staged
  points reach ``staging_points``, a power of two itself, so it holds a
  little more than that and pads to twice it: a resident point costs
  ``POINT_BYTES`` = 26 B, half of it padding (the default budget of
  1 << 26 points is 1.74 GB, 1 << 28 is 6.98 GB of a v5e chip's 16).
  The window accounts what it holds (``devwindow.bytes`` in /stats) and
  the daemon checks the budget against the device at boot
  (``require_fits``). The zone maps and the series directory stay on
  the host and cost the device nothing.

No reference analog: HBase scans are the reference's only read path
(src/core/TsdbQuery.java:240-285); this is the TPU-era replacement for
"the data lives next to the compute".
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time as _time
from typing import NamedTuple

import numpy as np

from opentsdb_tpu.obs import trace as _trace

LOG = logging.getLogger(__name__)


def _pad_pow2(n: int, lo: int = 1024) -> int:
    size = lo
    while size < n:
        size *= 2
    return size


# Slots per zone-map block (a power of two, so it divides every padded
# chunk at least as long; a shorter chunk is one block). One constant:
# the map is built with it at upload and DevChunks.block hands it to
# the fold, which slices by it.
ZONE_BLOCK = 1 << 16

# What one slot of a chunk holds on the device: the four columns of
# _upload (int32 rel_ts, float32 value, int32 sid, bool valid).
SLOT_BYTES = 4 + 4 + 4 + 1
# What one resident point can cost: _pad_pow2 pads a chunk of 2^k + 1
# points to 2^(k+1) slots, and a chunk cut at ``staging_points`` = 2^k
# staged points is such a chunk (the refill's are 1,048,680 points in
# 2,097,152 slots).
POINT_BYTES = 2 * SLOT_BYTES


def window_bytes(max_points: int, staging_points: int) -> int:
    """The most a window of this budget holds on the device: the budget
    and, for the moment between an upload and the eviction that follows
    it, one chunk more, with one further batch in the uploader's
    hands, every point at ``POINT_BYTES``."""
    return (max_points + 2 * staging_points) * POINT_BYTES


def require_fits(max_points: int, staging_points: int, stage_bytes: int,
                 bytes_limit: int | None) -> None:
    """Refuse a budget the device cannot hold: ``window_bytes`` of it
    beside ``stage_bytes`` (what a query's stage allocates next to the
    window) against ``bytes_limit``, the device's own statement of its
    memory. No statement (a CPU backend makes none), no check: an
    allocation there is the host's to page."""
    if bytes_limit is None:
        return
    need = window_bytes(max_points, staging_points) + stage_bytes
    if need > bytes_limit:
        fits = max((bytes_limit - stage_bytes) // POINT_BYTES
                   - 2 * staging_points, 0)
        raise ValueError(
            f"a device window of {max_points:,} points needs "
            f"{need:,} bytes on the device ({POINT_BYTES} B a resident "
            f"point, chunks of {staging_points:,} points, "
            f"{stage_bytes:,} B for a query's stage beside it) and the "
            f"device has {bytes_limit:,}; the largest budget that fits "
            f"is {fits:,} points")


def record_device_memory(collector, device) -> None:
    """The gauges ``device.bytes_limit`` / ``.bytes_in_use`` /
    ``.peak_bytes_in_use``: what the device a window's chunks live on
    says of its memory, asked now (beside ``devwindow.bytes``: the
    window's share of it). A device that states no limit, as a CPU
    backend's, records none."""
    from opentsdb_tpu.utils import jaxenv

    for name, value in (jaxenv.device_memory(device) or {}).items():
        collector.record("device." + name, value)


def chunks_cut(window) -> int:
    """Chunks cut from staged points since ``window`` (plain or
    sharded) began: uploaded or on their way, evicted ones included."""
    return sum(s._seq for s in getattr(window, "_shards", (window,)))


class DevColumns(NamedTuple):
    """One metric's resident window, ready for the fused kernels."""
    rel_ts: object          # [N] int32 device, seconds since ``epoch``
    values: object          # [N] float32 device
    sid: object             # [N] int32 device
    valid: object           # [N] bool device (padding mask)
    epoch: int              # int64 base the rel timestamps offset from
    series_keys: list       # sid -> series_key bytes
    generation: int         # bumps when the directory grows
    version: int            # bumps on ANY data change (new/evicted
    #                         chunks) — derived-result cache key


class ZoneMap(NamedTuple):
    """One chunk's map: an entry a block that holds a valid slot (the
    all-padding blocks at a chunk's end have none, so nothing selects
    them), each the least and greatest timestamp and the least and
    greatest series id of the block's valid slots."""
    tmin: np.ndarray
    tmax: np.ndarray
    smin: np.ndarray
    smax: np.ndarray

    def select(self, start: int, end: int, sids=None) -> np.ndarray:
        """Ids of the blocks whose [tmin, tmax] meets [start, end] and,
        given the sorted ``sids``, whose [smin, smax] holds one of
        them."""
        hit = (self.tmax >= start) & (self.tmin <= end)
        if sids is not None:
            hit &= (np.searchsorted(sids, self.smin, "left")
                    < np.searchsorted(sids, self.smax, "right"))
        return np.flatnonzero(hit).astype(np.int32)


def _zone_map(ts: np.ndarray, sid: np.ndarray, pad: int) -> ZoneMap:
    """The map of a chunk whose valid slots (its first len(ts)) hold
    ``ts`` and ``sid``: blocks of min(ZONE_BLOCK, pad) slots."""
    starts = np.arange(0, len(ts), min(ZONE_BLOCK, pad))
    return ZoneMap(np.minimum.reduceat(ts, starts),
                   np.maximum.reduceat(ts, starts),
                   np.minimum.reduceat(sid, starts),
                   np.maximum.reduceat(sid, starts))


class DevChunks(NamedTuple):
    """One metric's resident window as its RAW device chunk list — no
    concatenation. The chunked query stage (ops/kernels
    window_series_stage_chunks) folds these into [S, B] grids with
    per-chunk transients, so a window can approach the chip's whole
    HBM: the concat view costs a second full copy of the columns plus
    N-sized kernel transients, which caps it near half the HBM.

    ``blocks`` is the zone-map selection for the range the caller asked
    about, and for the series it matched if it said which. Every chunk
    keeps, for each block of slots, the least and greatest timestamp
    and series id of the block's valid slots (``ZoneMap``, recorded
    from the host arrays at upload). ``blocks[i]`` lists, ascending,
    the blocks of ``chunks[i]`` whose [tmin, tmax] meets [start, end];
    it is empty for a chunk the range cannot hit. ``narrowed``
    cuts the selection further, to the blocks that can also hold one of
    the matched series. Both are exact for any order of data: a block
    left out holds only slots that are padding, out of range or of a
    series nobody asked about. The time cut saves work wherever data is
    clustered in time (the refill's [metric][hour][series] order, live
    ingest's time-major slices), the series cut wherever a series'
    slots lie together and a row-hour of all the series spans several
    blocks (a run of 360 a series a row-hour in the refill's order: one
    host of 4,000 lies in 1 block of a row-hour's 22).

    The stage of a selection on time alone is whole: good for any
    filter. The grids of a narrowed one are whole for the matched
    series ONLY: it must never serve another filter. ``chunks`` is
    still every chunk, whole, for a caller that wants that."""
    chunks: list            # [(rel_ts, values, sid, valid) device arrays]
    epoch: int
    series_keys: list
    generation: int
    version: int
    blocks: list            # per chunk: int32 ids of the blocks picked
    block: int              # slots a block id stands for (ZONE_BLOCK)
    zones: list             # per chunk: its ZoneMap
    window: object = None   # the DeviceWindow the chunks live in

    # A stage is built over a window's shards (query/resident.py); one
    # window's columns are their own single shard, as devshard.py's
    # ShardedDevChunks lists several.
    shards = property(lambda self: [self])

    def narrowed(self, sids: np.ndarray, start: int,
                 end: int) -> "DevChunks":
        """This selection (made on time alone, for [start, end]) cut to
        the sorted series ids ``sids``: the blocks in range that can
        hold one of them. ``self`` where they cut no block out: every
        series matched, or data in which every block in range holds
        some matched series (a row-hour of all the series shorter than
        a block, or slots in no order of series)."""
        if len(sids) >= len(self.series_keys):
            return self
        # Only the chunks the range can hit at all are looked at.
        blocks = [zone.select(start, end, sids) if len(ids) else ids
                  for zone, ids in zip(self.zones, self.blocks)]
        if sum(map(len, blocks)) == sum(map(len, self.blocks)):
            return self
        return self._replace(blocks=blocks)

    def fold_extent(self) -> tuple[int, int, int, int, int, int]:
        """What the selection hands the fold: (blocks picked, blocks in
        all, slots picked, slots in all, chunks with a block picked,
        the window.chunk_fold calls the stage issues for them: one a
        group of chunks of one shape class, kernels.fold_groups, and
        none for a chunk with no block picked). Slots count the chunks'
        padding too: a skipped slot is skipped whatever it held."""
        from opentsdb_tpu.ops import kernels
        picked = of = visited = resident = hit = 0
        for chunk, ids in zip(self.chunks, self.blocks):
            slots = int(chunk[0].shape[0])
            blk = min(self.block, slots)
            picked += len(ids)
            of += slots // blk
            visited += len(ids) * blk
            resident += slots
            hit += len(ids) > 0
        calls = len(kernels.fold_groups(self.chunks, self.blocks,
                                        self.block))
        return picked, of, visited, resident, hit, calls


class _MetricWindow:
    __slots__ = ("sids", "keys", "last_ts", "epoch", "chunks",
                 "staged_ts", "staged_vals", "staged_sid", "staged_n",
                 "dirty", "complete_from", "concat", "generation",
                 "version", "device_points", "device_bytes", "inflight",
                 "inflight_since")

    def __init__(self) -> None:
        self.sids: dict[bytes, int] = {}
        self.keys: list[bytes] = []
        self.last_ts = np.full(64, -1, np.int64)   # by sid, grown
        #                                            by doubling
        self.epoch: int | None = None
        self.chunks: list[dict] = []      # ts/vals/sid device + n/max_ts
        #                                   + the zone map
        self.staged_ts: list[np.ndarray] = []
        self.staged_vals: list[np.ndarray] = []
        self.staged_sid: list[np.ndarray] = []
        self.staged_n = 0
        self.dirty = False
        self.complete_from: int | None = None  # None = since forever
        self.concat: DevColumns | None = None
        self.generation = 0
        self.version = 0          # bumps on ANY data change (chunk
        #                           appended/evicted, invalidate) —
        #                           derived-result cache key
        self.device_points = 0
        self.device_bytes = 0           # of its chunks' device columns
        self.inflight = 0               # taken-but-not-uploaded batches
        # Monotonic time of THIS metric's last upload progress while it
        # has in-flight batches (None = quiescent): the per-metric
        # wedge detector, immune to other metrics' completions keeping
        # the global liveness signal fresh.
        self.inflight_since: float | None = None


class DeviceWindow:
    """Thread-safe store of per-metric device-resident columns."""

    _instances = 0
    n_shards = 1    # (devshard.py's sharded window: as many as it has)

    def __init__(self, staging_points: int = 1 << 20,
                 max_points: int = 1 << 26,
                 background: bool = True,
                 stall_timeout: float = 60.0,
                 device=None) -> None:
        # Process-unique instance token: DevColumns.version counters
        # restart at 0 in a replacement window, so derived-result caches
        # key on (instance_id, version) to survive window swaps.
        DeviceWindow._instances += 1
        self.instance_id = DeviceWindow._instances
        self.staging_points = staging_points
        self.max_points = max_points
        self.background = background
        # Optional device pin: a mesh shard's window commits its chunks
        # to one specific device, so the stage kernels that consume the
        # committed inputs execute there — the per-shard placement the
        # sharded hot set (storage/devshard.py) is built on. None keeps
        # the historical behavior (jax's default device).
        self.device = device
        # Degraded-mode guard: a wedged accelerator (hung transport)
        # freezes the uploader mid-device-call FOREVER. Ingest and
        # queries must not hang with it — after stall_timeout they
        # dirty-mark the affected metric and proceed (queries fall back
        # to the storage scan path; the mark is sticky like every other
        # fallback). The reference's analog is the HBase-down drain
        # posture: degrade, never block the write path indefinitely.
        self.stall_timeout = stall_timeout
        self._lock = threading.RLock()
        self._metrics: dict[bytes, _MetricWindow] = {}
        # Background uploader: host->device copies of staged chunks run
        # off the ingest thread (the copy otherwise blocks ingest for
        # its full duration). Bounded queue = backpressure;
        # single worker = chunk order (and so per-series time order in
        # the concatenated window) is preserved.
        import queue as _queue

        self._pending: _queue.Queue = _queue.Queue(maxsize=2)
        self._uploader: threading.Thread | None = None
        # Per-metric upload completion: queries wait only for THEIR
        # metric's in-flight batches, not the whole queue (joining the
        # global queue couples query latency to unrelated ingest bursts).
        self._cond = threading.Condition(self._lock)
        # Global residency accounting: max_points caps the SUM across
        # metrics (the HBM budget is per chip, not per metric). Chunks
        # carry an upload sequence number, which orders a metric's own
        # chunks; eviction picks the chunk with the oldest DATA
        # fleet-wide (_evict_over_budget).
        self._total_points = 0
        self._total_bytes = 0           # of the resident chunks' columns
        # The padded sizes of every chunk uploaded so far, over all the
        # metrics (it only grows; replaced whole, so read without the
        # lock): the shape classes a stage's fold is compiled for.
        self.chunk_sizes: frozenset = frozenset()
        self._seq = 0
        # Liveness signal: bumps on EVERY upload completion (success or
        # failure). Stall handling keys off this, not off elapsed time
        # alone — a backlogged-but-progressing uploader (big chunks,
        # slow transport) must produce backpressure or a cache miss,
        # never the sticky dirty mark reserved for a wedged device
        # (ADVICE r03: a transient slowdown was a permanent cache loss).
        self._uploads_completed = 0
        # stats
        self.appended_points = 0
        self.evicted_points = 0
        self.dirty_fallbacks = 0
        self.upload_stalls = 0
        self.window_hits = 0
        self.window_misses = 0
        self.horizon_misses = 0
        # Why this thread's last columns() / chunk_columns() declined
        # (last_miss()): a query thread reads its own verdict, never a
        # neighbour's.
        self._why = threading.local()

    # -- ingest side ---------------------------------------------------

    def append(self, metric_uid: bytes, series_key: bytes,
               timestamps: np.ndarray, values: np.ndarray) -> None:
        """Record one series batch (timestamps int64 sorted ascending,
        values float64/float32): the one-series case of
        ``append_many``."""
        self.append_many(metric_uid, (series_key,), None, timestamps,
                         values)

    def append_rows(self, metric_uid: bytes, series_keys, counts,
                    timestamps: np.ndarray, values: np.ndarray) -> None:
        """A run of whole rows of distinct series of a metric, row
        ``i`` the next ``counts[i]`` points (the boot's refill from
        columnar blocks), recorded as ``append`` would record them a
        row at a time: a chunk is cut after the row that fills the
        staging batch, wherever in the run that row lies, so the
        window's chunks do not depend on how the store framed its
        rows. One ``append_many`` a chunk the run reaches into."""
        ends = np.cumsum(counts)
        i, n = 0, len(ends)
        while i < n:
            with self._lock:
                mw = self._metrics.get(metric_uid)
                room = self.staging_points - (mw.staged_n if mw else 0)
            a = int(ends[i - 1]) if i else 0
            k = min(int(np.searchsorted(ends, a + room, "left")), n - 1)
            z = int(ends[k])
            self.append_many(
                metric_uid, series_keys[i:k + 1],
                np.repeat(np.arange(k + 1 - i), counts[i:k + 1]),
                timestamps[a:z], values[a:z])
            i = k + 1

    def append_many(self, metric_uid: bytes, series_keys,
                    series_of_point: np.ndarray | None,
                    timestamps: np.ndarray, values: np.ndarray) -> None:
        """Record one batch of many series of a metric in one lock turn
        and ONE staged triple: ``series_of_point[i]`` indexes
        ``series_keys`` (distinct), points sorted by (that index,
        timestamp) with timestamps strictly ascending within a series;
        None is all points of ``series_keys[0]``. O(series) dict probes
        and a handful of array operations on the host, plus a device
        upload every ``staging_points`` points."""
        n = len(timestamps)
        if n == 0:
            return
        with self._lock:
            mw = self._metrics.get(metric_uid)
            if mw is None:
                mw = self._metrics[metric_uid] = _MetricWindow()
            if mw.dirty:
                return
            sids = mw.sids
            first_new = len(mw.keys)
            ids = [sids.get(k) for k in series_keys]
            if None in ids:
                for j, k in enumerate(series_keys):
                    if ids[j] is None:
                        ids[j] = sids[k] = len(mw.keys)
                        mw.keys.append(k)
                mw.generation += len(mw.keys) - first_new
                if len(mw.keys) > len(mw.last_ts):
                    grown = np.full(max(2 * len(mw.last_ts),
                                        len(mw.keys)), -1, np.int64)
                    grown[:first_new] = mw.last_ts[:first_new]
                    mw.last_ts = grown
            ts = np.array(timestamps, np.int64)
            if series_of_point is None:
                # One series (the boot's refill: a call a row-hour):
                # the same check on scalars.
                ids = ids[0]
                first, lasts = ts[0], ts[-1]
                sid = np.full(n, ids, np.int32)
                stale = first <= mw.last_ts[ids]
            else:
                # Series absent from this batch's points (none, from a
                # caller that lists the distinct series of its points)
                # drop out here: bincount gives them no run.
                counts = np.bincount(series_of_point,
                                     minlength=len(ids))
                have = counts > 0
                ends = np.cumsum(counts)[have]
                ids = np.asarray(ids, np.int64)
                sid = ids[series_of_point].astype(np.int32)
                ids = ids[have]
                lasts = ts[ends - 1]
                stale = (ts[ends - counts[have]] <= mw.last_ts[ids]).any()
            if stale:
                # Out-of-order or rewritten timestamp: correctness now
                # needs storage's dedup/overwrite semantics. Mark the
                # metric dirty and free its device state — every query
                # falls back to the scan path from here on.
                self._mark_dirty(mw)
                return
            mw.last_ts[ids] = lasts
            if mw.epoch is None:
                # The batch's first point, as a one-series-at-a-time
                # feed of the same points would have set it.
                mw.epoch = int(ts[0])
            # Stage COPIES: the window owns its buffers. asarray would
            # alias a caller's array of the right dtype, and since
            # sort_dedup's sorted fast path started returning the
            # ingest input by reference, a collector reusing its batch
            # buffer would silently rewrite staged timestamps under
            # the window. The memcpy is ~12 B/point, noise next to the
            # upload it feeds.
            mw.staged_ts.append(ts)
            mw.staged_vals.append(np.array(values, np.float32))
            mw.staged_sid.append(sid)
            mw.staged_n += n
            self.appended_points += n
            work = (self._take_staged(mw)
                    if mw.staged_n >= self.staging_points else None)
        # The bounded put happens OUTSIDE _lock: the uploader takes the
        # lock to append finished chunks, so blocking on a full queue
        # while holding it would deadlock.
        if work is not None:
            self._submit(work)

    def _take_staged(self, mw: _MetricWindow):
        """Swap the staged batch out (caller holds _lock); the returned
        work item is submitted outside the lock. The upload sequence
        number is assigned HERE, under the lock, so racing producers
        can't enqueue a metric's batches out of time order (_upload
        inserts by seq; eviction relies on chunks[0] being oldest)."""
        if mw.staged_n == 0:
            return None
        batch = (mw.staged_ts, mw.staged_vals, mw.staged_sid,
                 mw.staged_n)
        mw.staged_ts, mw.staged_vals, mw.staged_sid = [], [], []
        mw.staged_n = 0
        if mw.inflight == 0:
            mw.inflight_since = _time.monotonic()
        mw.inflight += 1
        seq = self._seq
        self._seq += 1
        return (mw, batch, seq)

    def _run_upload(self, work) -> None:
        """Execute one upload on the calling thread with full failure
        handling (dirty-mark under the lock) and completion signalling.
        Must be called without _lock."""
        try:
            self._upload(*work)
        except Exception:
            # Availability contract: the metric degrades to the scan
            # path (sticky dirty mark) instead of failing ingest — but
            # never without a word (HBM exhausted, a dtype the backend
            # refuses).
            LOG.exception("devwindow upload failed; metric marked "
                          "dirty, its queries fall back to the scan "
                          "path")
            with self._lock:
                self._mark_dirty(work[0])
        finally:
            self._upload_done(work[0])

    def _submit(self, work) -> None:
        """Queue one (mw, batch, seq) for the uploader thread, or upload
        inline when background=False. Must be called without _lock."""
        if not self.background:
            self._run_upload(work)
            return
        if self._uploader is None:
            with self._lock:
                if self._uploader is None:
                    self._uploader = threading.Thread(
                        target=self._upload_loop, daemon=True,
                        name="devwindow-uploader")
                    self._uploader.start()
        import queue as _queue
        while True:
            with self._cond:
                base = self._uploads_completed
            try:
                self._pending.put(work, timeout=self.stall_timeout)
                return
            except _queue.Full:
                with self._cond:
                    if (self._uploads_completed != base
                            and not self._metric_stuck(
                                work[0], _time.monotonic())):
                        # An upload finished during the wait: the
                        # uploader is alive, just backlogged. Keep
                        # blocking — a bounded queue IS the backpressure
                        # mechanism — rather than dirty-marking a
                        # healthy metric's whole window. (Unless THIS
                        # metric's own oldest batch is ancient — then
                        # it is stuck regardless of global liveness.)
                        continue
                    # No upload completed for a full stall window on a
                    # full queue: the device (or its transport) is
                    # wedged. Drop THIS metric to degraded mode instead
                    # of blocking the ingest thread behind a dead
                    # accelerator. The dropped work item's in-flight
                    # count (taken in _take_staged) must be released
                    # here — it will never reach _run_upload — or
                    # queries would wait on it forever.
                    mw = work[0]
                    self.upload_stalls += 1
                    self._mark_dirty(mw)
                    mw.inflight -= 1
                    self._cond.notify_all()
                    return

    def _upload_loop(self) -> None:
        while True:
            work = self._pending.get()
            try:
                # _run_upload dirty-marks under the lock on failure: a
                # bare flag write would leave resident chunks counting
                # toward _total_points forever (a dead window holding
                # HBM and forcing eviction of healthy metrics).
                self._run_upload(work)
            finally:
                self._pending.task_done()

    def _upload_done(self, mw: _MetricWindow) -> None:
        with self._cond:
            mw.inflight -= 1
            if mw.inflight == 0:
                mw.inflight_since = None
            else:
                # This metric itself made progress: restart its
                # per-metric wedge clock.
                mw.inflight_since = _time.monotonic()
            self._uploads_completed += 1
            self._cond.notify_all()

    @_trace.timed("devwindow.upload")
    def _upload(self, mw: _MetricWindow, batch, seq: int) -> None:
        """Upload one staged batch as a padded immutable chunk."""
        import jax

        staged_ts, staged_vals, staged_sid, _ = batch
        ts = np.concatenate(staged_ts)
        rel64 = ts - mw.epoch
        if (rel64 > 2**31 - 1).any() or (rel64 < -(2**31)).any():
            # >68 years from the metric's epoch: the int32 rel column
            # would wrap silently. Fall back rather than mis-bucket.
            with self._lock:
                self._mark_dirty(mw)
            return
        rel = rel64.astype(np.int32)
        vals = np.concatenate(staged_vals)
        sid = np.concatenate(staged_sid)
        n = len(rel)
        pad = _pad_pow2(n)
        zone = _zone_map(ts, sid, pad)
        if pad != n:
            rel = np.pad(rel, (0, pad - n))
            vals = np.pad(vals, (0, pad - n))
            sid = np.pad(sid, (0, pad - n))
        valid = np.arange(pad) < n
        dev = self.device
        chunk = {
            "ts": jax.device_put(rel, dev),
            "vals": jax.device_put(vals, dev),
            "sid": jax.device_put(sid, dev),
            "valid": jax.device_put(valid, dev),
            "n": n, "pad": pad, "seq": seq, "bytes": pad * SLOT_BYTES,
            "min_ts": int(zone.tmin.min()), "max_ts": int(zone.tmax.max()),
            "zone": zone,
        }
        with self._lock:
            if mw.dirty:  # marked dirty while we were copying
                return
            # Insert in seq order (assigned at _take_staged time). Two
            # things can land out of order here: racing producers whose
            # _pending.put() (outside the lock) inverts their take
            # order, and a query-side inline upload (columns()) racing
            # the background worker. Eviction relies on chunks[0] being
            # the metric's oldest.
            pos = len(mw.chunks)
            while pos > 0 and mw.chunks[pos - 1]["seq"] > seq:
                pos -= 1
            mw.chunks.insert(pos, chunk)
            if pad not in self.chunk_sizes:
                self.chunk_sizes = self.chunk_sizes | {pad}
            mw.device_points += n
            mw.device_bytes += chunk["bytes"]
            self._total_points += n
            self._total_bytes += chunk["bytes"]
            mw.concat = None
            mw.version += 1
            self._evict_over_budget(mw)

    def _evict_over_budget(self, mw: _MetricWindow) -> None:
        """Drop chunks until the (per-chip, NOT per-metric) budget
        holds; caller holds _lock and has just given ``mw`` a chunk.

        The victim is the metric whose oldest chunk holds the oldest
        data (least ``max_ts``; the upload sequence breaks ties, so
        time-major live ingest evicts in the order it always did). Its
        ``complete_from`` advances past everything the chunk could
        cover. By the data's age and not by arrival, so that a boot
        refill, which scans metric by metric, leaves what live ingest
        of the same points would have left: the newest chunks of every
        metric, their horizons within one chunk of each other."""
        while self._total_points > self.max_points:
            victim = min(
                (m for m in self._metrics.values() if m.chunks),
                key=lambda m: (m.chunks[0]["max_ts"], m.chunks[0]["seq"]),
                default=None)
            if victim is None or (victim is mw and len(mw.chunks) == 1):
                break  # never evict the chunk just added
            old = victim.chunks.pop(0)
            victim.device_points -= old["n"]
            victim.device_bytes -= old["bytes"]
            self._total_points -= old["n"]
            self._total_bytes -= old["bytes"]
            self.evicted_points += old["n"]
            victim.concat = None
            victim.version += 1
            nxt = old["max_ts"] + 1
            if (victim.complete_from is None
                    or nxt > victim.complete_from):
                victim.complete_from = nxt

    def flush(self) -> None:
        """Upload every metric's staged points and wait for the
        uploader to drain (query-side barrier)."""
        with self._lock:
            work = [w for w in map(self._take_staged,
                                   self._metrics.values()) if w]
        for w in work:
            self._submit(w)
        # Bounded barrier: join() would block forever if the uploader
        # is wedged inside a device call (task_done only fires after
        # the hung upload returns). Best-effort within stall_timeout.
        deadline = _time.monotonic() + self.stall_timeout
        while (self._pending.unfinished_tasks
               and _time.monotonic() < deadline):
            _time.sleep(0.01)

    def quiesce(self) -> None:
        """Materialize EVERYTHING into device chunks: upload all staged
        batches and wait for every metric's in-flight uploads. The
        reshard gate's drain step (devshard.py) — after it returns, a
        refs-only chunk snapshot is the complete window. A metric whose
        uploads stall past the wedge deadline degrades to dirty (the
        standard sticky fallback) rather than blocking forever."""
        self.flush()
        deadline = _time.monotonic() + 2 * self.stall_timeout
        with self._cond:
            while any(mw.inflight > 0 and not mw.dirty
                      for mw in self._metrics.values()):
                now = _time.monotonic()
                if now >= deadline:
                    for mw in self._metrics.values():
                        if mw.inflight > 0 and not mw.dirty:
                            self.upload_stalls += 1
                            self._mark_dirty(mw)
                    self._cond.notify_all()
                    break
                self._cond.wait(timeout=min(deadline - now, 0.05))

    def _snapshot_metrics(self) -> dict:
        """Refs-only snapshot for the reshard rebuild (devshard.py):
        per metric, the directory, chunk list, and coverage state at
        this instant. Chunks are immutable once inserted, so holding
        refs is safe; the caller must treat every field as read-only.
        Call after ``quiesce`` — staged/in-flight batches are not
        represented."""
        with self._lock:
            return {uid: {"keys": list(mw.keys),
                          "epoch": mw.epoch,
                          "chunks": list(mw.chunks),
                          "dirty": mw.dirty,
                          "complete_from": mw.complete_from}
                    for uid, mw in self._metrics.items()}

    def set_complete_from(self, metric_uid: bytes, floor: int) -> None:
        """Raise (never lower) a metric's coverage floor — the reshard
        rebuild carries the source shards' eviction horizon into the
        redistributed window so it never claims coverage the old set
        had already evicted."""
        with self._lock:
            mw = self._metrics.get(metric_uid)
            if mw is None:
                return
            if mw.complete_from is None or floor > mw.complete_from:
                mw.complete_from = floor

    def invalidate(self, metric_uid: bytes | None = None) -> None:
        """Mark window state unusable after storage mutations the append
        stream didn't see (deletes, fsck --fix rewrites, mid-batch
        throttles). The mark is sticky — popping the window instead
        would let the next append recreate one that claims coverage
        since forever while storage holds data it never saw."""
        with self._lock:
            targets = (list(self._metrics.values()) if metric_uid is None
                       else filter(None, [self._metrics.get(metric_uid)]))
            for mw in targets:
                self._mark_dirty(mw)

    def _mark_dirty(self, mw: _MetricWindow) -> None:
        """Sticky fallback mark + free the metric's device/staging state.
        Caller holds _lock."""
        mw.dirty = True
        mw.chunks.clear()
        mw.concat = None
        mw.version += 1
        mw.staged_ts.clear()
        mw.staged_vals.clear()
        mw.staged_sid.clear()
        mw.staged_n = 0
        self._total_points -= mw.device_points
        self._total_bytes -= mw.device_bytes
        mw.device_points = mw.device_bytes = 0

    # -- query side ----------------------------------------------------

    def _wait_quiet(self, mw: _MetricWindow) -> str:
        """Wait for this metric's in-flight uploads with the
        wedged-vs-slow distinction (ADVICE r03): the sticky dirty mark
        is reserved for a device that has completed NOTHING for a full
        stall window; a backlogged-but-progressing uploader yields a
        bounded plain miss instead (scan fallback now, window intact
        for the next query). Returns ``"ready"`` (quiescent — caller
        still re-checks dirty under the lock), or ``"slow"``.

        Progress = ``_uploads_completed`` advancing, ANY metric: device
        calls mostly serialize, so a completion is evidence the
        transport is alive. But it is not proof THIS metric's upload
        moves (a query-drain helper can be stuck in its own device call
        while the uploader thread completes others), so a per-metric
        hard deadline — ``inflight_since`` older than 4x stall_timeout
        — converts a persistently-stuck metric to sticky dirty no
        matter how fresh the global signal is; without it, every query
        of that metric would pay the 2x cap forever. ``dirty``
        short-circuits — an already-degraded metric answers
        immediately, not after a stall_timeout per query."""

        with self._cond:
            last = self._uploads_completed
            now = _time.monotonic()
            deadline = now + self.stall_timeout       # wedge detector
            cap = now + 2 * self.stall_timeout        # latency bound
            while mw.inflight > 0 and not mw.dirty:
                now = _time.monotonic()
                if self._uploads_completed != last:
                    last = self._uploads_completed
                    deadline = now + self.stall_timeout
                if now >= deadline or self._metric_stuck(mw, now):
                    # Nothing completed for a full stall window while
                    # we held in-flight work: wedged. Degrade this
                    # metric so the query (and every later one) takes
                    # the scan path instead of hanging on a dead
                    # device. Wake the other waiters — their loop
                    # re-checks dirty.
                    self.upload_stalls += 1
                    self._mark_dirty(mw)
                    self._cond.notify_all()
                    break
                if now >= cap:
                    return "slow"
                self._cond.wait(timeout=min(deadline, cap) - now)
        return "ready"

    def _metric_stuck(self, mw: _MetricWindow, now: float) -> bool:
        """True when THIS metric's oldest in-flight batch has made no
        progress for 4x stall_timeout — the per-metric wedge verdict
        that global upload completions cannot mask. Caller holds
        _cond/_lock."""
        return (mw.inflight_since is not None
                and now - mw.inflight_since >= 4 * self.stall_timeout)

    @contextlib.contextmanager
    def _ready_window(self, metric_uid: bytes, start: int):
        """The shared availability preamble of columns()/chunk_columns()
        as a context manager: drain this metric's staged batch, wait for
        ITS in-flight uploads, validate the exact-coverage contract.
        Yields the window WITH THE LOCK HELD (released on exit, every
        path — the old hand-off-a-held-lock contract deadlocked if any
        future early return forgot the release), or None for scan-path
        fallback."""
        self._why.reason = None
        with self._lock:
            mw = self._metrics.get(metric_uid)
            if mw is None:
                self.window_misses += 1
                self._why.reason = "absent"
                yield None
                return
            work = self._take_staged(mw)
        # Upload + drain OUTSIDE the lock (the uploader takes the
        # lock to append chunks); then re-check under the lock —
        # the drain can mark dirty (upload failure) or advance
        # complete_from. The query's staged batch uploads INLINE
        # (not via the queue: queueing would couple this query's
        # latency to other metrics' stuck uploads — ADVICE r02) but
        # on a daemon helper thread: a device call wedged inside
        # the transport cannot be interrupted, so the query thread
        # must never make it directly. The helper's batch counts in
        # mw.inflight (released in _run_upload's finally), so the
        # unified _wait_quiet below applies the same wedged-vs-slow
        # policy to it; a parked helper is a bounded daemon-thread
        # leak, and if the device later revives and the upload
        # lands, _upload's dirty check discards it.
        if work is not None:
            threading.Thread(target=self._run_upload, args=(work,),
                             daemon=True,
                             name="devwindow-query-drain").start()
        if self._wait_quiet(mw) == "slow":
            with self._lock:       # counters mutate under the lock only
                self.window_misses += 1
            self._why.reason = "slow"
            yield None
            return
        with self._lock:
            if mw.dirty:
                self.dirty_fallbacks += 1
                self._why.reason = "dirty"
                yield None
            elif (mw.complete_from is not None
                    and start < mw.complete_from):
                self.window_misses += 1
                self.horizon_misses += 1
                self._why.reason = "horizon"
                yield None
            elif not mw.chunks:
                self.window_misses += 1
                self._why.reason = "absent"
                yield None
            else:
                yield mw

    def last_miss(self) -> str | None:
        """Why this thread's last columns() / chunk_columns() declined:
        ``horizon`` (the range starts before the metric's
        ``complete_from``), ``dirty``, ``absent`` (no window, or none
        of its chunks left) or ``slow`` (uploads still in flight after
        the wait); None after a hit."""
        return getattr(self._why, "reason", None)

    def horizons(self) -> tuple[int, int] | None:
        """Least and greatest ``complete_from`` over the metrics that
        hold data: how far apart eviction has left them. A metric that
        has lost nothing counts as 0, so (0, 0) where nothing was
        evicted; None where no metric holds data."""
        with self._lock:
            froms = [mw.complete_from or 0
                     for mw in self._metrics.values() if mw.chunks]
        return (min(froms), max(froms)) if froms else None

    def columns(self, metric_uid: bytes, start: int,
                end: int) -> DevColumns | None:
        """The metric's resident columns when they exactly cover
        [start, end]; None means the caller must use the scan path."""
        with self._ready_window(metric_uid, start) as mw:
            if mw is None:
                return None
            if mw.concat is None or mw.concat.generation != mw.generation:
                import jax.numpy as jnp

                mw.concat = DevColumns(
                    rel_ts=jnp.concatenate(
                        [c["ts"] for c in mw.chunks]),
                    values=jnp.concatenate(
                        [c["vals"] for c in mw.chunks]),
                    sid=jnp.concatenate([c["sid"] for c in mw.chunks]),
                    valid=jnp.concatenate(
                        [c["valid"] for c in mw.chunks]),
                    epoch=mw.epoch, series_keys=list(mw.keys),
                    generation=mw.generation,
                    version=mw.version)
            self.window_hits += 1
            return mw.concat

    def chunk_columns(self, metric_uid: bytes, start: int,
                      end: int) -> DevChunks | None:
        """Like columns(), but returns the raw chunk list without
        building (or caching) the concatenated view — the chunked query
        stage folds it without a second full copy of the columns. Same
        availability contract: None means scan-path fallback.

        Beside every chunk goes the zone-map selection for [start, end]
        (DevChunks.blocks): the blocks whose recorded [min, max]
        timestamp meets the range. A caller that then learns which
        series the request matched (from the directory this returns)
        cuts it further with ``DevChunks.narrowed``. It decides what
        the fold visits, never what is available, and never leaves out
        a slot in range whatever order the data came in."""
        with self._ready_window(metric_uid, start) as mw:
            if mw is None:
                return None
            self.window_hits += 1
            return DevChunks(
                chunks=[(c["ts"], c["vals"], c["sid"], c["valid"])
                        for c in mw.chunks],
                epoch=mw.epoch, series_keys=list(mw.keys),
                generation=mw.generation, version=mw.version,
                blocks=[c["zone"].select(start, end) for c in mw.chunks],
                block=ZONE_BLOCK, zones=[c["zone"] for c in mw.chunks],
                window=self)

    def chunk_classes(self) -> list:
        """One resident chunk (its four columns) of each padded size,
        over all the metrics: what a stage folds, with nothing to
        visit, to have every program its kind can run compiled on this
        window's device before a request needs it (the sharded
        window's stages, query/executor.py)."""
        with self._lock:
            found = {c["pad"]: c for mw in self._metrics.values()
                     for c in mw.chunks}
        return [(c["ts"], c["vals"], c["sid"], c["valid"])
                for c in found.values()]

    # -- observability -------------------------------------------------

    def collect_stats(self, collector, device: bool = True) -> None:
        collector.record("devwindow.points.appended", self.appended_points)
        collector.record("devwindow.points.evicted", self.evicted_points)
        collector.record("devwindow.hits", self.window_hits)
        collector.record("devwindow.misses", self.window_misses)
        collector.record("devwindow.misses.horizon", self.horizon_misses)
        collector.record("devwindow.dirty_fallbacks", self.dirty_fallbacks)
        collector.record("devwindow.upload_stalls", self.upload_stalls)
        collector.record("devwindow.points.budget", self.max_points)
        lo, hi = self.horizons() or (0, 0)
        collector.record("devwindow.horizon.min", lo)
        collector.record("devwindow.horizon.max", hi)
        with self._lock:
            collector.record("devwindow.metrics", len(self._metrics))
            collector.record(
                "devwindow.points.resident",
                sum(mw.device_points for mw in self._metrics.values()))
            # What the resident chunks' columns hold on the device,
            # padding included.
            collector.record("devwindow.bytes", self._total_bytes)
            # Chunks resident over all metrics: a stage dispatches one
            # fold for each of a metric's chunks its range can hit, and
            # a read that drains a metric's staged points cuts one.
            collector.record(
                "devwindow.chunks",
                sum(len(mw.chunks) for mw in self._metrics.values()))
        if device:
            record_device_memory(collector, self.device)
