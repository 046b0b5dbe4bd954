"""Device-side cache of decoded block columns for the fused path.

The byte-stream fused leg (a mesh's) re-uploads and re-decodes every
covering block's payload streams on each dispatch. This cache keeps
the QUERY-INDEPENDENT decoded columns of single BLOCKS resident on
device — per-point qualifier deltas (int32) and decoded values
(float32) — a block a row of two [slots, P_BLK] slabs, 8 bytes a
point. A query over warm blocks uploads per-RECORD arrays for whole blocks (base time, series id,
validity: two orders of magnitude smaller than the point stream) or,
where a selector keeps a small part of the blocks it touches,
per-point arrays for the matched points alone, and runs
compress/kernels.slab_stage_rows / slab_stage_sel with zero payload
bytes moved. A cold block's streams go up as the file holds them —
packed nibbles, payload bytes, points a record, ~4 bytes a point —
and one program (slab_fill) decodes a batch of blocks into their rows.

Entries are single blocks, so every gather that touches a block shares
its decode: a dashboard's panels over one host, the next host of the
same rack, the fleet-wide overview. (Entries used to be whole gathers,
keyed by their block set: one narrow request after another over drawn
hosts then shared nothing and decoded 13 blocks to read a hundredth of
them, and a gather over the bound was never kept at all.) What made
whole gathers attractive — a compile and a dispatch per distinct block
shape — is gone with fixed shapes: a row holds any block of the store
(P_BLK and R_BLK are the largest block's points and records when the
cache opens, rounded up; a gather with a later block that is larger
is declined to the raw plan), a fill is always FILL_BLOCKS rows with
payload buffers for the worst case (4 bytes a point), so there is ONE fill
program a value codec, and the stages compile by the count of blocks
(dense leg) or of matched points (selective leg), each padded to a
power of two.

Holding the SSTable OBJECTS in the key both identifies the generation
and pins it against id reuse — a dropped generation's blocks age out
of the LRU, they can never alias a new file. The bound is total cached
POINTS (Config.devblock_points: what the deployment states, held by
the daemon to half of what the device has left beside its window), the
slabs are allocated once, when the first gather arrives, for the
blocks the store then holds and an eighth more as far as the bound
allows, and never grow: past that, least-recently-used blocks make
room and are decoded again when they are next asked for.

The slabs are updated in place (donated to slab_fill), so a fill and
the dispatch of a stage that reads them exclude each other: ``stage``
holds the cache's lock from the lookup to the end of the caller's
dispatch (asynchronous, so for the host's part of it only).

Answers are bit-identical to the byte-stream fused program: identical
decode math (the XOR/delta chains never cross block boundaries),
identical point order, and a row's padding points decode to a zero
delta and belong to a pad record every query marks invalid.

Counters: compress.devcache.{hit,miss,evict} count blocks,
compress.devcache.uploaded_bytes what the fills sent up; the gauge
compress.devcache.bytes is what the slabs hold of the device. A
request's fills are the span fused.fill under fused.dispatch (tags:
blocks, evicted).
"""

from __future__ import annotations

import itertools
import threading
import weakref

import numpy as np

from opentsdb_tpu.compress import codecs
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS

_HIT = METRICS.counter("compress.devcache.hit")
_MISS = METRICS.counter("compress.devcache.miss")
_EVICT = METRICS.counter("compress.devcache.evict")
# Bytes the fills sent to the device: nibbles, payload buffers as
# padded, points a record.
_UPLOADED = METRICS.counter("compress.devcache.uploaded_bytes")

# Blocks a fill program decodes; a short batch pads with dropped rows.
FILL_BLOCKS = 8
# Bytes a cached point holds on the device: delta, value.
POINT_BYTES = 8

_LIVE: "weakref.WeakSet[DeviceBlockCache]" = weakref.WeakSet()
METRICS.gauge("compress.devcache.bytes",
              lambda: sum(c.nbytes for c in list(_LIVE)))


def pad_pow2(n: int, lo: int = 1) -> int:
    p = lo
    while p < n:
        p <<= 1
    return p


def pad_fine(n: int) -> int:
    """Smallest of {2^k, 1.25*2^k, 1.5*2^k, 1.75*2^k} >= n (k >= 6):
    the byte-stream leg's point-stream size ladder. Pow-of-two padding
    wastes up to 2x decode+stage compute on the padding tail; quarter
    steps cap the waste at 25% while keeping the compile-shape space
    to four classes per octave."""
    p = 64
    while p < n:
        p <<= 1
    h = p >> 1
    for m in (h * 5) >> 2, (h * 3) >> 1, (h * 7) >> 2:
        if m >= n:
            return m
    return p


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


class DeviceBlockCache:
    """Bounded LRU of decoded blocks in two device slabs."""

    def __init__(self, max_points: int) -> None:
        self.max_points = int(max_points)
        self._lock = threading.Lock()
        self._slot: dict[tuple, int] = {}    # (sst, j) -> row, LRU order
        self._free: list[int] = []
        self.P_BLK = self.R_BLK = self.slots = 0
        self._qd = self._vals = None
        self._opened = False
        _LIVE.add(self)

    def __len__(self) -> int:
        return len(self._slot)

    @property
    def nbytes(self) -> int:
        return self.slots * self.P_BLK * POINT_BYTES

    def _open(self, ssts) -> None:
        """Size the slabs from the generations the first gather meets:
        a row holds the largest TSF32/TSINT block they have, and there
        are rows for all of them and an eighth more, as far as
        ``max_points`` allows."""
        import jax.numpy as jnp
        self._opened = True
        most_p = most_n = blocks = 0
        for sst in {id(s): s for s in ssts}.values():
            for j in range(sst.block_count):
                if sst.block_header(j)[0] in (codecs.TSF32,
                                              codecs.TSINT):
                    n, p = codecs._HDR.unpack_from(
                        sst.block_enc(j), 0)[:2]
                    most_p, most_n = max(most_p, p), max(most_n, n)
                    blocks += 1
        # One point more than the largest block: a row ends in padding.
        self.P_BLK = _round_up(most_p + 1, 1024)
        self.R_BLK = _round_up(most_n + 1, 128)
        self.slots = min(self.max_points // self.P_BLK,
                         _round_up(blocks + blocks // 8, 64))
        if self.slots < 1:
            return
        shape = (self.slots, self.P_BLK)
        self._qd = jnp.zeros(shape, jnp.int32)
        self._vals = jnp.zeros(shape, jnp.float32)
        self._free = list(range(self.slots - 1, -1, -1))

    def held(self, src) -> int:
        """How many of the gather's blocks are decoded here now (a
        span's tag: no lock, and it moves nothing in the LRU)."""
        table = self._slot
        return sum((sst, j) in table for sst, j, _p in src.blocks)

    def stage(self, src, run):
        """``run(qd, vals, slots)`` with the gather's blocks in
        the slabs, ``slots`` their rows in the gather's order; None
        when the slabs cannot hold the gather (more blocks than rows,
        or a block larger than a row), which the plan then declines
        (``oversize``). ``run`` dispatches the stage and returns."""
        with self._lock:
            if not self._opened:
                self._open(s for s, _lo, _hi in src.spans)
            slots = self._ensure(src)
            if slots is None:
                return None
            return run(self._qd, self._vals, slots)

    def _ensure(self, src) -> "np.ndarray | None":
        blocks = src.blocks
        if len(blocks) > self.slots or any(
                p.P >= self.P_BLK or p.n >= self.R_BLK
                for _s, _j, p in blocks):
            return None
        table = self._slot
        keys = [(sst, j) for sst, j, _p in blocks]
        need = set(keys)
        missing = []
        for k, key in enumerate(keys):
            row = table.pop(key, None)
            if row is None:
                missing.append(k)
            else:
                table[key] = row        # most recently used
        _HIT.inc(len(keys) - len(missing))
        if missing:
            _MISS.inc(len(missing))
            with obs_trace.span("fused.fill") as sp:
                short = max(len(missing) - len(self._free), 0)
                if short:
                    # The oldest first; never a block of this gather.
                    for key in list(itertools.islice(
                            (k for k in table if k not in need), short)):
                        self._free.append(table.pop(key))
                    _EVICT.inc(short)
                for k in missing:
                    table[keys[k]] = self._free.pop()
                for a in range(0, len(missing), FILL_BLOCKS):
                    self._fill([blocks[k] for k in
                                missing[a:a + FILL_BLOCKS]], src.kind)
                if sp is not None:
                    sp.tags.update(blocks=len(missing), evicted=short)
        return np.fromiter((table[key] for key in keys), np.int32,
                           len(keys))

    def _fill(self, batch, kind: str) -> None:
        from opentsdb_tpu.compress import kernels as _ck
        P, R, B = self.P_BLK, self.R_BLK, FILL_BLOCKS
        slots = np.full(B, self.slots, np.int32)     # past the end: dropped
        ts_nib = np.zeros((B, P // 2), np.uint8)
        v_nib = np.zeros((B, P // 2), np.uint8)
        # Past a block's payload no byte is read under a live mask.
        ts_pay = np.empty((B, P * 4), np.uint8)
        v_pay = np.empty((B, P * 4), np.uint8)
        npts = np.zeros((B, R), np.int32)
        for b, (sst, j, prep) in enumerate(batch):
            s = codecs.ts_block_streams(sst.block_enc(j))
            slots[b] = self._slot[(sst, j)]
            ts_nib[b, :len(s.ts_nib)] = s.ts_nib
            v_nib[b, :len(s.v_nib)] = s.v_nib
            ts_pay[b, :len(s.ts_pay)] = s.ts_pay
            v_pay[b, :len(s.v_pay)] = s.v_pay
            npts[b, :prep.n] = prep.npts
        _UPLOADED.inc(ts_nib.nbytes + v_nib.nbytes + ts_pay.nbytes
                      + v_pay.nbytes + npts.nbytes)
        self._qd, self._vals = _ck.slab_fill(
            self._qd, self._vals, slots, ts_nib, ts_pay, v_nib, v_pay,
            npts, vkind=kind)

    # -- per-query uploads ----------------------------------------------

    def record_inputs(self, src, slots: np.ndarray, S_cap: int):
        """The dense leg's uploads: (slots, starts, rel_base, sid,
        valid), the last four [K_pad, R_BLK] a record, K_pad the
        blocks padded to a power of two (16 at the least) with invalid
        rows that read row 0. ``starts`` is where in its row each
        record's points begin; past a block's last record it is where
        its padding begins, under valid=False. sid is clipped to
        S_cap - 1, mirroring the byte leg's padding discipline."""
        K, R = len(src.blocks), self.R_BLK
        K_pad = pad_pow2(K, 16)
        rows = np.zeros(K_pad, np.int32)
        rows[:K] = slots
        counts = np.diff(src.rec_off)
        at = np.repeat(np.arange(K, dtype=np.int64) * R
                       - src.rec_off[:-1], counts) \
            + np.arange(src.rec_off[-1])

        def spread(a, dtype):
            out = np.zeros(K_pad * R, dtype)
            out[at] = a
            return out.reshape(K_pad, R)

        npts = spread(src.npts, np.int32)
        return (rows, np.cumsum(npts, axis=1, dtype=np.int32) - npts,
                spread(src.rel_base, np.int32),
                spread(np.minimum(src.sid, S_cap - 1), np.int32),
                spread(src.valid, bool))

    def point_inputs(self, src, slots: np.ndarray, S_cap: int):
        """The selective leg's uploads, a matched point each: (row,
        col, rel_base, sid, valid), M_pad long (a power of two, 8,192
        at the least; padding reads point 0 under valid=False).
        ``row`` is the point's slab row, ``col`` its place in the
        block."""
        counts, npts = np.diff(src.rec_off), src.npts
        ends = np.cumsum(npts)
        # A record's first point in its block: the running count less
        # the count at its block's first record.
        first = ends - npts - np.repeat(
            (ends - npts)[src.rec_off[:-1]], counts)
        row = np.repeat(slots, counts)
        rec = np.flatnonzero(src.valid)
        n = npts[rec]
        M = int(n.sum())
        M_pad = pad_pow2(M, 8192)
        rep = np.repeat(np.arange(len(rec)), n)
        within = np.arange(M) - np.repeat(np.cumsum(n) - n, n)

        def padded(a, dtype):
            out = np.zeros(M_pad, dtype)
            out[:M] = a
            return out

        return (padded(row[rec][rep], np.int32),
                padded(first[rec][rep] + within, np.int32),
                padded(src.rel_base[rec][rep], np.int32),
                padded(np.minimum(src.sid[rec], S_cap - 1)[rep],
                       np.int32),
                padded(np.ones(M, bool), bool))
