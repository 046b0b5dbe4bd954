"""Device-resident streaming sketch state, folded in at ingest.

The reference's only streaming statistic is the fixed-bucket latency
Histogram (reference src/stats/Histogram.java:38), and distinct-value
questions require materializing every group at query time. Per the north
star (BASELINE.json) this layer replaces both with mergeable sketches that
live in device memory (HBM on TPU) and are updated as data arrives:

- one t-digest per series (value distribution -> p50/p95/p99 without a
  storage rescan),
- one HyperLogLog register bank per (metric, tag key) pair (distinct tag
  values, e.g. "how many hosts report sys.cpu.user").

Design (SURVEY.md §5.4, §7.4):

- **Fixed-shape stacks.** All digests live in two [C, K] arrays
  (means/weights), all HLLs in one [C, 2^p] int32 array; C doubles on
  demand. One extra trash row absorbs padded scatter indices, so every
  update is a fixed-shape jitted call regardless of how many sketches
  it touches.
- **Fold shapes follow the deployment, never the clock.** How many
  series and tag values a fold holds depends on when it runs (a
  checkpoint's snapshot folds whatever is buffered), so a fold is cut
  into batches of a few fixed shapes (``_fold_rows``, ``_HLL_ROWS`` x
  ``_HLL_ITEMS``): a width class's first fold compiles its shapes, on
  the folder thread, and no later one compiles.
- **Buffered folding with a staleness bound.** ``observe()`` appends to a
  host-side buffer (O(1), no device work on the ingest hot path); full
  buffers hand off to a background folder thread (bounded queue, so a
  device that can't keep up backpressures ingest instead of growing an
  unbounded backlog), keeping device latency entirely off the ingest
  critical path — on real TPU hardware the fold dispatches cost
  milliseconds each and were measured dominating ingest when inline.
  Queries drain the folder first, so answers are exact as of the query;
  the backlog is bounded by ``flush_points`` + the queue depth (the
  staleness bound) at all times.
- **Mergeability across chips.** States merge by elementwise max (HLL)
  and concatenate+recompress (t-digest) — ``merge_from`` for host-side
  fan-in; on a mesh the same merges ride pmax / all_gather
  (parallel/sharded.py sharded_hll_distinct, sharded_tdigest).
- **Checkpoint/resume.** ``save``/``load`` snapshot the device state to
  host .npz; TSDB.checkpoint writes the snapshot in the same window as
  the storage spill, so on crash recovery the snapshot covers exactly
  the sstable tier and re-folding the WAL-replayed memtable restores the
  rest. HLL recovery is exact under replay (register max is idempotent);
  t-digest recovery is approximate if a crash lands inside the
  checkpoint-commit window (a bounded double-fold) — acceptable for a
  sketch, and the tests pin the tolerance.
"""

from __future__ import annotations

import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.core.const import UID_WIDTH
from opentsdb_tpu.obs import trace as _trace
from opentsdb_tpu.ops import sketches

_PAD_MIN = 8


def _pad(n: int) -> int:
    size = _PAD_MIN
    while size < n:
        size *= 2
    return size


class LiveSketches:
    """Streaming sketch store; thread-safe (one lock around buffer+state).

    ``compression``: t-digest centroid budget per series (K).
    ``hll_p``: per-(metric, tagk) register count exponent (2^p int32).
    ``flush_points``: buffered-point bound before an automatic fold.
    """

    def __init__(self, compression: int = 128, hll_p: int = 12,
                 flush_points: int = 65536,
                 background: bool = True) -> None:
        self.compression = compression
        self.hll_p = hll_p
        self.flush_points = flush_points
        self.background = background
        self._lock = threading.RLock()
        # Guards the device stacks: the folder thread replaces them while
        # observers (holding only self._lock) keep buffering.
        self._state_lock = threading.RLock()
        # slot maps: key -> row in the device stacks
        self._td_slots: dict[bytes, int] = {}
        self._hll_slots: dict[tuple[bytes, bytes], int] = {}
        # Per-metric series directory (keys grouped by their metric
        # UID prefix): the executor's candidate-series hint reads one
        # metric's keys instead of filtering the whole directory.
        self._metric_series: dict[bytes, list[bytes]] = {}
        # device stacks ([capacity(+1 trash implied by scatter clamp), ...])
        self._td_means = jnp.zeros((_PAD_MIN, compression), jnp.float32)
        self._td_weights = jnp.zeros((_PAD_MIN, compression), jnp.float32)
        self._hll_regs = jnp.zeros((_PAD_MIN, 1 << hll_p), jnp.int32)
        # host-side buffers
        self._td_buf: list[tuple[np.ndarray, np.ndarray]] = []   # (slot
        #                               of each value, values) a batch
        self._hll_buf: dict[int, set[int]] = {}
        self._buffered = 0
        # (stack capacity, width class) pairs whose fold shapes this
        # process has run (_fold_td_rows).
        self._td_warm: set[tuple[int, int]] = set()
        # A store loaded from a snapshot (a daemon's boot) runs the fold
        # shapes of its first batch's width class before that batch
        # returns (_warm_first): see observe_many.
        self._warm_first = False
        # background folder: bounded queue of swapped-out buffer pairs
        import queue as _queue

        self._pending: _queue.Queue = _queue.Queue(maxsize=2)
        self._folder: threading.Thread | None = None
        self._fold_error: BaseException | None = None

    # -- slot management (host-only; capacity grows at fold time) ----------

    def _td_slot(self, series_key: bytes) -> int:
        slot = self._td_slots.get(series_key)
        if slot is None:
            slot = len(self._td_slots)
            self._td_slots[series_key] = slot
            self._metric_series.setdefault(
                series_key[:UID_WIDTH], []).append(series_key)
        return slot

    def _hll_slot(self, metric_uid: bytes, tagk_uid: bytes) -> int:
        key = (metric_uid, tagk_uid)
        slot = self._hll_slots.get(key)
        if slot is None:
            slot = len(self._hll_slots)
            self._hll_slots[key] = slot
        return slot

    def _ensure_capacity(self, td_rows: int, hll_rows: int) -> None:
        """Grow the device stacks to hold the given slot counts; caller
        holds _state_lock."""
        if td_rows > self._td_means.shape[0]:
            cap = _pad(td_rows)
            pad_rows = cap - self._td_means.shape[0]
            pad = jnp.zeros((pad_rows, self.compression), jnp.float32)
            self._td_means = jnp.concatenate([self._td_means, pad])
            self._td_weights = jnp.concatenate([self._td_weights, pad])
        if hll_rows > self._hll_regs.shape[0]:
            cap = _pad(hll_rows)
            self._hll_regs = jnp.concatenate([
                self._hll_regs,
                jnp.zeros((cap - self._hll_regs.shape[0],
                           1 << self.hll_p), jnp.int32)])

    # -- ingest-side API ---------------------------------------------------

    def note_series(self, series_key: bytes) -> None:
        """Register a series in the slot directory WITHOUT folding any
        values. The write path calls this BEFORE the storage put
        (core/tsdb.add_batch/add_point): the executor's bloom-pruning
        hint treats the directory as a complete superset of series
        with stored data, so no query may ever observe stored rows the
        directory lacks — including mid-batch-throttle aborts, whose
        applied cells would otherwise never register. The empty slot
        folds real values on the next successful batch."""
        with self._lock:
            self._td_slot(series_key)

    def metric_series_count(self, metric_uid: bytes) -> int:
        """Directory size for one metric (the hint cache's cheap
        revalidation key — a new series under a DIFFERENT metric no
        longer invalidates this metric's cached hint)."""
        with self._lock:
            return len(self._metric_series.get(metric_uid, ()))

    def metric_series_keys(self, metric_uid: bytes) -> list[bytes]:
        """Snapshot of one metric's series keys (no whole-directory
        filtering)."""
        with self._lock:
            return list(self._metric_series.get(metric_uid, ()))

    def observe(self, series_key: bytes, values: np.ndarray,
                tag_uids: list[tuple[bytes, bytes, bytes]]) -> None:
        """Record one series batch: the one-series case of
        ``observe_many``."""
        self.observe_many((series_key,), None, values, tag_uids)

    def observe_many(self, series_keys, series_of_point, values,
                     tag_uids) -> None:
        """Record one batch of many series: ``values[i]`` folds into the
        digest of ``series_keys[series_of_point[i]]`` (None: all into
        ``series_keys[0]``); each (metric_uid, tagk_uid, tagv_uid) folds
        the tag value into the pair's HLL. One lock turn, O(series) dict
        probes on the host; device folding is deferred to flush()."""
        with self._lock:
            n = len(values)
            if n:
                one = series_of_point is None
                slots = (self._td_slot(series_keys[0]) if one else
                         np.fromiter(map(self._td_slot, series_keys),
                                     np.int32, len(series_keys)))
                if self._warm_first:
                    self._warm_first = False
                    self._warm(n if one else int(
                        np.bincount(series_of_point).max()))
                self._td_buf.append(
                    (np.full(n, slots, np.int32) if one
                     else slots[series_of_point],
                     np.asarray(values, np.float32)))
                self._buffered += n
            for metric_uid, tagk_uid, tagv_uid in tag_uids:
                slot = self._hll_slot(metric_uid, tagk_uid)
                self._hll_buf.setdefault(slot, set()).add(
                    int.from_bytes(tagv_uid, "big"))
            if self._buffered >= self.flush_points:
                self._hand_off_locked()

    def _warm(self, longest: int) -> None:
        """Run, empty, the fold shapes a batch whose longest series has
        ``longest`` values will fold in, at the capacity the directory
        needs: a daemon booted on a store knows both from its first
        batch, and compiles there (a deployment's warm-up, the first
        write after a restart) what the folder thread would otherwise
        compile at some later moment, beside reads."""
        widths = self._FOLD_WIDTHS
        P = widths[int(np.searchsorted(
            widths, min(longest, self._MAX_CHUNK)))]
        none = np.empty(0, np.int64)
        with self._state_lock:
            self._ensure_capacity(len(self._td_slots),
                                  len(self._hll_slots))
            self._fold_td_rows(none, none, none, none, P)
            self._fold_hll_rows([], [])

    def _hand_off_locked(self) -> None:
        """Swap the buffers out and queue them for the folder thread
        (or fold inline when background=False). Caller holds _lock."""
        if not self._td_buf and not self._hll_buf:
            return
        td_buf, self._td_buf = self._td_buf, []
        hll_buf, self._hll_buf = self._hll_buf, {}
        self._buffered = 0
        if not self.background:
            self._fold_buffers(td_buf, hll_buf)
            return
        if self._folder is None:
            self._folder = threading.Thread(
                target=self._fold_loop, daemon=True,
                name="sketch-folder")
            self._folder.start()
        # Bounded put: a device that can't keep up backpressures the
        # ingest thread here instead of growing an unbounded backlog.
        self._pending.put((td_buf, hll_buf))

    def _fold_loop(self) -> None:
        while True:
            td_buf, hll_buf = self._pending.get()
            try:
                self._fold_buffers(td_buf, hll_buf)
            except BaseException as e:  # surfaced on the next flush()
                self._fold_error = e
            finally:
                self._pending.task_done()

    def flush(self) -> None:
        """Fold every buffered observation into the device state and
        wait for the folder to drain (queries call this first, so their
        answers are exact as of the call)."""
        with self._lock:
            self._hand_off_locked()
        self._pending.join()
        if self._fold_error is not None:
            err, self._fold_error = self._fold_error, None
            raise err

    # Fold-batch bounds: a series' values fold ``_MAX_CHUNK`` at a
    # time, each piece padded to a width of ``_FOLD_WIDTHS``, and a fold
    # call holds a fixed number of rows of one width (``_fold_rows``),
    # so flush memory is O(total buffered points), never (series x
    # longest-series), and the shapes a process compiles are those few
    # whatever a fold happens to hold.
    _MAX_CHUNK = 4096
    _FOLD_WIDTHS = tuple(8 << k for k in range(10))      # 8 .. 4096
    _FOLD_CELLS = 1 << 17
    _FOLD_ROWS_SMALL = 64
    _HLL_ROWS = 64
    _HLL_ITEMS = 512

    @classmethod
    def _fold_rows(cls, P: int) -> tuple[int, ...]:
        """The row counts a fold of width ``P`` comes in: the batch of
        a full buffer, and a small one for the handful of series a
        query's or a snapshot's flush may find."""
        big = max(cls._FOLD_CELLS // P, 1)
        return ((cls._FOLD_ROWS_SMALL, big)
                if big > cls._FOLD_ROWS_SMALL else (big,))

    def _fold_td(self, slots: np.ndarray, row_of: np.ndarray,
                 pos: np.ndarray, vals: np.ndarray, S: int,
                 P: int) -> None:
        """One fold call of shape (S, P): row r takes slot ``slots[r]``
        (rows past them scatter out of bounds and are dropped), value i
        sits at ``[row_of[i], pos[i]]``."""
        batch = np.zeros((S, P), np.float32)
        valid = np.zeros((S, P), bool)
        batch[row_of, pos] = vals
        valid[row_of, pos] = True
        idx = np.full(S, self._td_means.shape[0], np.int32)
        idx[:len(slots)] = slots
        self._td_means, self._td_weights = _fold_tdigests(
            self._td_means, self._td_weights, jnp.asarray(idx),
            jnp.asarray(batch), jnp.asarray(valid),
            compression=self.compression)

    @_trace.timed("sketch.fold")
    def _fold_buffers(self, td_buf: list, hll_buf: dict) -> None:
        """Fold one swapped-out buffer pair into the device stacks.
        Runs on the folder thread (or inline when background=False);
        serialized by _state_lock."""
        with self._state_lock:
            if td_buf:
                slot = np.concatenate([s for s, _ in td_buf])
                vals = np.concatenate([v for _, v in td_buf])
                self._ensure_capacity(int(slot.max()) + 1, 0)
                # By slot, values in arrival order; a slot's values are
                # cut into pieces of _MAX_CHUNK, and round k folds every
                # slot's k-th piece (scatter indices must be unique
                # within a fold), a width class at a time.
                if (slot[1:] < slot[:-1]).any():
                    order = np.argsort(slot, kind="stable")
                    slot, vals = slot[order], vals[order]
                first = np.flatnonzero(
                    np.concatenate(([True], slot[1:] != slot[:-1])))
                counts = np.diff(np.append(first, len(slot)))
                rank = np.arange(len(slot)) - np.repeat(first, counts)
                piece = rank // self._MAX_CHUNK
                pos = rank % self._MAX_CHUNK
                widths = np.asarray(self._FOLD_WIDTHS)
                for k in range(int(piece.max()) + 1):
                    pts = np.flatnonzero(piece == k)
                    # The rows of this round: one a slot that has a
                    # k-th piece, its length deciding its width class.
                    starts = pts[pos[pts] == 0]
                    lens = np.minimum(counts[np.searchsorted(
                        first, starts, "right") - 1]
                        - k * self._MAX_CHUNK, self._MAX_CHUNK)
                    klass = np.searchsorted(widths, lens)
                    row_of_pt = np.repeat(np.arange(len(starts)), lens)
                    klass_of_pt = klass[row_of_pt]
                    for c in np.unique(klass):
                        rows = np.flatnonzero(klass == c)
                        mine = pts[klass_of_pt == c]
                        self._fold_td_rows(
                            slot[starts[rows]],
                            np.searchsorted(rows, row_of_pt[klass_of_pt == c]),
                            pos[mine], vals[mine], int(widths[c]))
            if hll_buf:
                self._ensure_capacity(0, max(hll_buf) + 1)
                # Rows of at most _HLL_ITEMS values of one slot; a slot
                # with more takes several (register max is idempotent,
                # so a slot may recur within a fold).
                U = self._HLL_ITEMS
                row_slot: list[int] = []
                row_items: list[np.ndarray] = []
                for s in sorted(hll_buf):
                    u = np.fromiter(hll_buf[s], np.int32)
                    for off in range(0, len(u), U):
                        row_slot.append(s)
                        row_items.append(u[off:off + U])
                self._fold_hll_rows(row_slot, row_items)

    def _fold_hll_rows(self, row_slot: list, row_items: list) -> None:
        """Fold rows of tag values (row r: ``row_items[r]`` into slot
        ``row_slot[r]``) in calls of the one HLL shape; with no row,
        one empty call (that compiles it)."""
        H, U = self._HLL_ROWS, self._HLL_ITEMS
        for lo in range(0, max(len(row_slot), 1), H):
            items = np.zeros((H, U), np.int32)
            valid = np.zeros((H, U), bool)
            idx = np.full(H, self._hll_regs.shape[0], np.int32)
            for r, u in enumerate(row_items[lo:lo + H]):
                items[r, :len(u)] = u
                valid[r, :len(u)] = True
                idx[r] = row_slot[lo + r]
            self._hll_regs = _fold_hlls(
                self._hll_regs, jnp.asarray(idx), jnp.asarray(items),
                jnp.asarray(valid), p=self.hll_p)

    def _fold_td_rows(self, slots: np.ndarray, row_of: np.ndarray,
                      pos: np.ndarray, vals: np.ndarray, P: int) -> None:
        """Fold rows of one width class ``P`` (row r takes slot
        ``slots[r]``; value i sits at ``[row_of[i], pos[i]]``, rows
        ascending) in calls of that class's fixed shapes. The first
        fold of a class in this process (and of this stack capacity)
        also runs the class's other shape, empty, so that whatever a
        later fold holds finds its program compiled."""
        shapes = self._fold_rows(P)
        warm = (self._td_means.shape[0], P)
        if warm not in self._td_warm:
            self._td_warm.add(warm)
            none = np.empty(0, np.int64)
            for S in shapes:
                self._fold_td(none, none, none, none, S, P)
        big = shapes[-1]
        for lo in range(0, len(slots), big):
            hi = min(lo + big, len(slots))
            S = next(s for s in shapes if s >= hi - lo)
            a, b = np.searchsorted(row_of, (lo, hi))
            self._fold_td(slots[lo:hi], row_of[a:b] - lo, pos[a:b],
                          vals[a:b], S, P)

    # -- query-side API ----------------------------------------------------

    def distinct(self, metric_uid: bytes, tagk_uid: bytes) -> int | None:
        """Streaming distinct-tagv estimate; None when the pair was never
        ingested. Flushes first, so the answer is current."""
        with self._lock:
            slot = self._hll_slots.get((metric_uid, tagk_uid))
            if slot is None:
                return None
            # Holding _lock blocks new hand-offs; flush() drains the
            # folder, so the stacks are stable for the read below.
            self.flush()
            if slot >= self._hll_regs.shape[0]:
                return 0  # slot assigned but never folded
            return int(round(float(
                sketches.hll_estimate(self._hll_regs[slot]))))

    def quantile(self, series_keys: list[bytes], q) -> np.ndarray | None:
        """Quantiles of the merged all-time distribution of the given
        series (one digest concatenate+recompress). None when no listed
        series has sketch state. ``q`` scalar or [K]; returns [K]."""
        with self._lock:
            slots = [self._td_slots[k] for k in series_keys
                     if k in self._td_slots]
            if not slots:
                return None
            self.flush()
            with self._state_lock:
                self._ensure_capacity(max(slots) + 1, 0)
            S = _pad(len(slots))
            idx = np.zeros(S, np.int32)
            idx[:len(slots)] = slots
            valid = np.zeros(S, bool)
            valid[:len(slots)] = True
            out = _merged_quantile(
                self._td_means, self._td_weights, jnp.asarray(idx),
                jnp.asarray(valid),
                jnp.atleast_1d(jnp.asarray(q, jnp.float32)),
                compression=self.compression)
            return np.asarray(out)

    def series_count(self) -> int:
        return len(self._td_slots)

    def series_keys(self) -> list[bytes]:
        """All series with sketch state — the slot map doubles as a
        series directory, so sketch queries select series without any
        storage scan."""
        with self._lock:
            return list(self._td_slots)

    # -- merge / checkpoint ------------------------------------------------

    def merge_from(self, other: "LiveSketches") -> None:
        """Fold another store's state in (multi-chip / multi-host fan-in:
        each shard folds its own series locally, the query side merges —
        register max for HLL, centroid recompress for digests; the mesh
        form of the same merges is parallel/sharded.py)."""
        with self._lock, other._lock:
            other.flush()
            self.flush()
            # Pre-assign every incoming slot, then grow once: slot
            # creation no longer grows the stacks inline (fold-time
            # concern), so indexing below must be in capacity.
            for key in other._td_slots:
                self._td_slot(key)
            for key in other._hll_slots:
                self._hll_slot(*key)
            with self._state_lock:
                self._ensure_capacity(len(self._td_slots),
                                      len(self._hll_slots))
            with other._state_lock:
                other._ensure_capacity(len(other._td_slots),
                                       len(other._hll_slots))
            for key, oslot in other._td_slots.items():
                slot = self._td_slot(key)
                m, w = sketches.tdigest_merge(
                    self._td_means[slot], self._td_weights[slot],
                    other._td_means[oslot], other._td_weights[oslot],
                    compression=self.compression)
                self._td_means = self._td_means.at[slot].set(m)
                self._td_weights = self._td_weights.at[slot].set(w)
            for key, oslot in other._hll_slots.items():
                slot = self._hll_slot(*key)
                self._hll_regs = self._hll_regs.at[slot].set(
                    jnp.maximum(self._hll_regs[slot],
                                other._hll_regs[oslot]))

    def save(self, path: str) -> None:
        """Snapshot device state to a host .npz (atomic via tmp+rename).

        Only the hand-off of what is buffered and the copy of the slot
        maps hold the lock ``observe`` takes; the folds the snapshot
        waits for, the device-to-host copy and the file run beside
        ingest. Observations that arrive meanwhile may be in the
        snapshot or not: it then covers more than storage's spill will,
        the over-cover the checkpoint order already accepts (exact for
        HLLs, within tolerance for digests)."""
        self.flush()
        with self._state_lock:
            stacks = (self._td_means, self._td_weights, self._hll_regs)
        # The slot maps AFTER the stacks: a slot is assigned before its
        # values are buffered, so every row of the stacks that holds
        # data has its key here, and a key whose row the stacks lack
        # yet gets a zero row below.
        with self._lock:
            td_keys = sorted(self._td_slots, key=self._td_slots.get)
            hll_keys = sorted(self._hll_slots, key=self._hll_slots.get)
        means, weights, regs = (np.asarray(a) for a in stacks)

        def cover(a: np.ndarray, rows: int) -> np.ndarray:
            short = _pad(rows) - a.shape[0]
            return a if short <= 0 else np.pad(a, ((0, short), (0, 0)))

        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(
                f,
                td_keys=np.array(td_keys, dtype=object),
                hll_metric=np.array([k[0] for k in hll_keys],
                                    dtype=object),
                hll_tagk=np.array([k[1] for k in hll_keys],
                                  dtype=object),
                td_means=cover(means, len(td_keys)),
                td_weights=cover(weights, len(td_keys)),
                hll_regs=cover(regs, len(hll_keys)),
                meta=np.array([self.compression, self.hll_p]))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str, flush_points: int = 65536) -> "LiveSketches":
        z = np.load(path, allow_pickle=True)
        compression, hll_p = (int(x) for x in z["meta"])
        self = cls(compression=compression, hll_p=hll_p,
                   flush_points=flush_points)
        self._td_means = jnp.asarray(z["td_means"])
        self._td_weights = jnp.asarray(z["td_weights"])
        self._hll_regs = jnp.asarray(z["hll_regs"])
        self._td_slots = {bytes(k): i for i, k in enumerate(z["td_keys"])}
        for k in self._td_slots:
            self._metric_series.setdefault(k[:UID_WIDTH], []).append(k)
        self._hll_slots = {
            (bytes(m), bytes(t)): i
            for i, (m, t) in enumerate(zip(z["hll_metric"], z["hll_tagk"]))}
        self._warm_first = True
        return self


# ---------------------------------------------------------------------------
# Jitted batch folds (fixed shapes; cached per (stack, batch) padded size)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("compression",))
def _fold_tdigests(means, weights, idx, batch, valid, *, compression):
    """Gather rows at idx, fold each row's batch, scatter back. Padded
    idx entries point one past the stack and scatter with mode='drop';
    their gathers clamp to the last row but the result is discarded."""
    m_rows = means[jnp.clip(idx, 0, means.shape[0] - 1)]
    w_rows = weights[jnp.clip(idx, 0, means.shape[0] - 1)]
    new_m, new_w = jax.vmap(
        lambda m, w, v, ok: sketches.tdigest_add(
            m, w, v, ok, compression=compression))(
                m_rows, w_rows, batch, valid)
    return (means.at[idx].set(new_m, mode="drop"),
            weights.at[idx].set(new_w, mode="drop"))


@functools.partial(jax.jit, static_argnames=("p",))
def _fold_hlls(regs, idx, items, valid, *, p):
    rows = regs[jnp.clip(idx, 0, regs.shape[0] - 1)]
    new = jax.vmap(
        lambda r, it, ok: sketches.hll_add(r, it, ok, p=p))(
            rows, items, valid)
    return regs.at[idx].max(new, mode="drop")


@functools.partial(jax.jit, static_argnames=("compression",))
def _merged_quantile(means, weights, idx, valid, q, *, compression):
    m = jnp.where(valid[:, None], means[idx], 0.0).reshape(-1)
    w = jnp.where(valid[:, None], weights[idx], 0.0).reshape(-1)
    mm, ww = sketches._compress(m, w, compression=compression)
    return sketches.tdigest_quantile(mm, ww, q)
