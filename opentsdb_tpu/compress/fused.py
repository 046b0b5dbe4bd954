"""Query-side source for the fused decode-aggregate path.

``gather`` decides whether a [start, end] range of one metric can be
served straight from TSST4 blocks — exact-or-decline, the devwindow
contract: every generation holding range keys is v4 with disjoint key
ranges (store.encoded_range), every covering block is a TSF32 or
TSINT columnar block (one kind per gather — the stage's value inverse
is a compile-time static), and the caller has verified no
memtable-resident data overlaps the range (executor chunk_state). On
success it returns the blocks that hold matching in-range records,
their per-RECORD arrays (base time, series id, validity) concatenated
in block order, and the block-discovered series directory with the
group segment map the apply kernels consume directly. Nothing
point-sized is built here: the device block cache decodes a block's
streams as the file holds them (compress/devcache.py), and only the
byte-stream leg asks for the concatenated point arrays
(``FusedSource.point_stream``).

Declines raise ``Decline`` with a stable reason string — the executor
counts every one under compress.fused.decline{reason=} before falling
back to the scan path, so no decline is ever silent.

Host cost discipline:
- which blocks: a selector whose matching series are KNOWN (the
  executor's series hint, the raw plan's seek) finds its rows by
  binary search in the generation's key index, one probe a (series,
  row-hour), and touches only the blocks those rows lie in; without a
  hint the range's blocks are walked and the selector runs against
  their prefix-compressed keys, once a distinct series;
- keys: parsed per block once (codecs.parse_ts_block keys_only) into
  per-record arrays and the generation's series directory;
- payload: one pass over a block's nibble streams the first time a
  gather keeps it (the kernels index at most 4 payload bytes a point;
  a TSINT block's values must fit int32), its verdict kept;
- qualifier-delta bounds (the duplicate-row overlay check): computed
  only when duplicate row keys are actually present across
  generations (single-generation gathers never pay it — sstable keys
  are unique within one file).
Everything parsed is cached on the (immutable) SSTable object and is
record-sized: a few KB a block, whatever the block's points.
"""

from __future__ import annotations

import threading
from bisect import bisect_left

import numpy as np

from opentsdb_tpu.compress import codecs
from opentsdb_tpu.core.const import (MAX_TIMESPAN, TIMESTAMP_BYTES,
                                     UID_WIDTH)

_IDENT_LO = UID_WIDTH

_KIND = {codecs.TSF32: "f32", codecs.TSINT: "int"}


class Decline(Exception):
    """The fused path cannot serve this gather; ``reason`` is the
    stable label the executor counts under
    compress.fused.decline{reason=}. Always a correctness decline —
    the scan path serves the identical answer."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class _BlockPrep:
    """Per-RECORD host arrays of one TSF32/TSINT block, independent of
    any query: base time, metric, the record's series as an id in its
    generation's directory, points a record. The payload's verdict and
    the delta bounds load lazily."""

    __slots__ = ("kind", "n", "P", "base", "metric", "gid", "npts",
                 "pay_bytes", "_why", "_dmin", "_dmax")

    def __init__(self):
        self._why = None         # None=unchecked, True=ok, str=reason
        self._dmin = None
        self._dmax = None

    @property
    def first_pt(self) -> np.ndarray:
        return np.cumsum(self.npts) - self.npts

    def check_payload(self, sst, j: int) -> "str | None":
        """None when the kernels can consume the block's streams, else
        the decline reason; decided once."""
        if self._why is None:
            self._why = self._check(sst, j)
        return None if self._why is True else self._why

    def _check(self, sst, j: int):
        try:
            s = codecs.ts_block_streams(sst.block_enc(j))
        except Exception:
            return "block-ineligible"
        if s.max_nb > 4:
            return "block-ineligible"
        if self.kind == "int" and self.P:
            # The device inverse is an int32 modular cumsum cast to
            # f32; it is bit-exact iff every decoded value fits int32
            # (and the per-point deltas do too — implied by nb <= 4
            # checked above plus the value bound here).
            vals = self.parsed(sst, j).int_values()
            if (int(vals.min()) < -(2**31)
                    or int(vals.max()) > 2**31 - 1):
                return "int-overflow"
        self.pay_bytes = max(len(s.ts_pay), len(s.v_pay))
        return True

    def parsed(self, sst, j: int) -> codecs.TsBlock:
        tag, _raw_len, _enc_len = sst.block_header(j)
        return codecs.parse_ts_block(tag, sst.block_enc(j))

    def delta_bounds(self, sst, j: int):
        """Per-record qualifier-delta (min, max): the overlay check
        for a row-hour split across generations by a mid-hour
        checkpoint (disjoint delta ranges => the overlay is a pure
        union the kernel computes naturally). Lazy — only duplicate
        row keys across generations ever need it."""
        if self._dmin is None:
            deltas = self.parsed(sst, j).deltas()
            first = self.first_pt
            self._dmin = np.minimum.reduceat(deltas, first)
            self._dmax = np.maximum.reduceat(deltas, first)
        return self._dmin, self._dmax


class _SstDir:
    """What the fused path keeps of one generation's ``table``: the
    records' raw offsets and the blocks' as arrays (a row key's place
    in the index gives its block, and less ``first_key``, the place of
    that block's first record, its record), the directory of the
    series its parsed blocks hold (series key <-> gid, in the order
    met), and the parsed blocks."""

    __slots__ = ("offs", "blk_raw", "first_key", "skeys", "gids",
                 "preps", "lock")

    def __init__(self, sst, table: str):
        _keys, offs = sst._index[table]
        self.offs = np.asarray(offs, np.int64)
        self.blk_raw = np.asarray(sst._blk_raw, np.int64)
        self.first_key = np.searchsorted(self.offs, self.blk_raw,
                                         "left")
        self.skeys: list[bytes] = []
        self.gids: dict[bytes, int] = {}
        self.preps: dict[int, "_BlockPrep | None"] = {}
        # Two requests may parse one block at once; the directory's
        # ids are handed out one thread at a time.
        self.lock = threading.Lock()


def _sst_dir(sst, table: str) -> _SstDir:
    dirs = sst.__dict__.setdefault("_fused_dir", {})
    d = dirs.get(table)
    if d is None:
        d = dirs[table] = _SstDir(sst, table)
    return d


def _prep(sst, d: _SstDir, j: int, table: str) -> "_BlockPrep | None":
    """Parse block ``j``'s keys once; None when the block is not a
    TSF32/TSINT data block of ``table`` (caller declines)."""
    if j in d.preps:
        return d.preps[j]
    try:
        tag, _raw_len, _enc_len = sst.block_header(j)
        b = codecs.parse_ts_block(tag, sst.block_enc(j),
                                  keys_only=True) \
            if tag in _KIND else None
    except Exception:
        b = None
    return _prep_of(d, j, table, b)


def _prep_of(d: _SstDir, j: int, table: str, b) -> "_BlockPrep | None":
    """The prep of block ``j`` from its parsed keys (None: not a
    columnar block), entered in the generation's directory."""
    prep = None
    ident = b.identity() if b is not None \
        and b.table == table.encode() else None
    if ident is not None:
        prep = _BlockPrep()
        prep.kind = _KIND[b.tag]
        prep.n, prep.P = b.n, b.P
        prep.npts = b.npts.astype(np.int32)
        prep.metric, prep.base, skeys = ident
        gids, known = d.gids, d.skeys
        gid = np.empty(b.n, np.int32)
        with d.lock:
            for i, sk in enumerate(skeys):
                g = gids.get(sk)
                if g is None:
                    g = gids[sk] = len(known)
                    known.append(sk)
                gid[i] = g
        prep.gid = gid
    return d.preps.setdefault(j, prep)


def prime(sst, table: str, j: int, b) -> None:
    """Enter a block the boot refill has parsed (``b``, with its
    keys) in the generation's directory, so that no request pays for
    parsing it again."""
    _prep_of(_sst_dir(sst, table), j, table, b)


def block_range(sst, table: str, lo: int, hi: int) -> range:
    """The blocks that hold ``table``'s keys [lo, hi) of the
    generation's index: a table's records are one run of the file, so
    they are the blocks between the range's ends."""
    d = _sst_dir(sst, table)
    j_lo, j_hi = np.searchsorted(
        d.blk_raw, d.offs[[lo, hi - 1]], "right") - 1
    return range(int(j_lo), int(j_hi) + 1)


class PointStream:
    """The concatenated per-point kernel inputs of a gather, for the
    byte-stream leg (compress/kernels.fused_block_stage): nibble byte
    counts and payload bytes of both streams, each point's record-first
    and block-first index, and its record's base time, series and
    validity."""

    __slots__ = ("ts_nb", "ts_pay", "v_nb", "v_pay", "first_idx",
                 "blk_first", "rel_base_pt", "sid_pt", "valid")


class FusedSource:
    """One (metric, range[, selector]) gather. ``blocks`` is
    [(sst, j, prep)], the blocks holding a matching in-range record,
    in key order; ``rec_off`` [K + 1] bounds each block's records in
    the concatenated per-record arrays ``rel_base`` (base time less
    ``epoch``), ``sid``, ``valid`` and ``npts`` (points a record). ``spans`` is the encoded_range
    snapshot the arrays were built FROM — the executor's stage cache
    keys on (and pins) exactly these SSTable objects, so a checkpoint
    racing the gather can never get a stale stage cached under the new
    generation set.

    ``kind`` is the gather's value codec ("f32"/"int") — the stage's
    ``vkind`` static. ``groups`` maps each selector group key to its
    sid list (sids ascend by series key within a group, matching the
    scan path's float32 row-sum order). ``npoints`` counts the points
    of the blocks touched, ``matched`` those of the valid records."""

    __slots__ = ("blocks", "rec_off", "rel_base", "sid", "valid",
                 "npts", "series_keys", "epoch", "npoints", "matched", "spans",
                 "kind", "groups")

    def payload_bytes(self) -> int:
        """The larger payload stream's bytes, summed over the blocks."""
        return sum(p.pay_bytes for _s, _j, p in self.blocks)

    def point_stream(self) -> PointStream:
        ps = PointStream()
        parts = [[] for _ in range(9)]
        pt_off = 0
        for k, (sst, j, prep) in enumerate(self.blocks):
            b = prep.parsed(sst, j)
            a, z = self.rec_off[k], self.rec_off[k + 1]
            rec = b.rec_of_pt
            for lst, arr in zip(parts, (
                    b.ts_nb.astype(np.int32), b.ts_pay,
                    b.v_nb.astype(np.int32), b.v_pay,
                    b.first_pt[rec] + pt_off,
                    np.full(b.P, pt_off, np.int64),
                    self.rel_base[a:z][rec], self.sid[a:z][rec],
                    self.valid[a:z][rec])):
                lst.append(arr)
            pt_off += b.P
        cat = [np.concatenate(p) for p in parts]
        (ps.ts_nb, ps.ts_pay, ps.v_nb, ps.v_pay, first_idx, blk_first,
         ps.rel_base_pt, ps.sid_pt, ps.valid) = cat
        ps.first_idx = first_idx.astype(np.int32)
        ps.blk_first = blk_first.astype(np.int32)
        return ps


def _empty(spans) -> FusedSource:
    src = FusedSource()
    src.npoints = src.matched = 0
    src.series_keys = []
    src.groups = {}
    src.blocks = []
    src.kind = "f32"
    src.spans = spans
    return src


def _seek(keys, lo: int, hi: int, series_keys, bases) -> np.ndarray:
    """Ascending index places in ``keys[lo:hi]`` of the row keys
    (series, base) that are there."""
    found = []
    for base in bases:
        b4 = base.to_bytes(TIMESTAMP_BYTES, "big")
        for sk in series_keys:
            key = sk[:_IDENT_LO] + b4 + sk[_IDENT_LO:]
            i = bisect_left(keys, key, lo, hi)
            if i < hi and keys[i] == key:
                found.append(i)
    found.sort()
    return np.asarray(found, np.int64)


def gather(store, table: str, metric_uid: bytes, b_lo: int,
           b_hi: int, selector=None, series_keys=None,
           sel_memo: "dict | None" = None) -> FusedSource:
    """Collect every block holding rows of ``metric_uid`` with base
    time in [b_lo, b_hi] from the store's v4 generations. Exact or
    ``Decline`` — any ineligible block, format, or overlay risk
    declines with a reason.

    ``selector(series_key) -> group_key_tuple | None`` is the pushed-
    down tag-filter/group-by predicate: it runs against the series
    keys of the blocks BEFORE any payload byte is touched (once a
    distinct series; ``sel_memo`` carries its verdicts from one gather
    of a filter to the next), non-matching records are masked out, and
    blocks with no matching in-range record are skipped entirely.
    ``series_keys``, when given, is every KNOWN series the selector
    matches (the executor's series hint: a superset of those stored):
    where that is few against the range's rows, the rows are sought in
    the key index and only their blocks are looked at."""
    start_key = metric_uid + b_lo.to_bytes(4, "big")
    stop_key = metric_uid + min(b_hi + MAX_TIMESPAN,
                                0xFFFFFFFF).to_bytes(4, "big")
    spans = store.encoded_range(table, start_key, stop_key)
    if spans is None:
        raise Decline("no-encoded-range")
    m = int.from_bytes(metric_uid, "big")
    memo = sel_memo if sel_memo is not None else {}

    def group_of(sk: bytes):
        if selector is None:
            return ()
        try:
            return memo[sk]
        except KeyError:
            g = memo[sk] = selector(sk)
            return g

    bases = range(b_lo, b_hi + 1, MAX_TIMESPAN)
    parts = []           # (sst, dir, [j], [prep], gid, base, valid)
    kinds: set[str] = set()
    for sst, lo, hi in spans:
        keys = sst._index[table][0]
        d = _sst_dir(sst, table)
        sought = None
        if series_keys is not None \
                and 4 * len(series_keys) * len(bases) < hi - lo:
            sought = _seek(keys, lo, hi, series_keys, bases)
            blk_of = np.searchsorted(d.blk_raw, d.offs[sought],
                                     "right") - 1
            blk_ids = np.unique(blk_of)
        else:
            blk_ids = np.asarray(block_range(sst, table, lo, hi))
        if not len(blk_ids):
            continue
        preps = []
        for j in blk_ids.tolist():
            prep = _prep(sst, d, j, table)
            if prep is None:
                raise Decline("block-ineligible")
            preps.append(prep)
        rec_off = np.zeros(len(preps) + 1, np.int64)
        np.cumsum([p.n for p in preps], out=rec_off[1:])
        gid = np.concatenate([p.gid for p in preps])
        base = np.concatenate([p.base for p in preps])
        if sought is not None:
            valid = np.zeros(len(gid), bool)
            valid[rec_off[np.searchsorted(blk_ids, blk_of)]
                  + sought - d.first_key[blk_of]] = True
        else:
            valid = (base >= b_lo) & (base <= b_hi) & (
                np.concatenate([p.metric for p in preps]) == m)
        if selector is not None and valid.any():
            keep = np.zeros(len(d.skeys), bool)
            cand = np.unique(gid[valid])
            keep[cand] = [group_of(d.skeys[g]) is not None
                          for g in cand.tolist()]
            valid &= keep[gid]
        # Drop the blocks left with no matching record.
        has = np.add.reduceat(valid, rec_off[:-1]) > 0
        if not has.any():
            continue
        if not has.all():
            pick = np.repeat(has, np.diff(rec_off))
            gid, base, valid = gid[pick], base[pick], valid[pick]
            blk_ids = blk_ids[has]
            preps = [p for p, h in zip(preps, has) if h]
        kinds.update(p.kind for p in preps)
        parts.append((sst, d, blk_ids.tolist(), preps, gid, base,
                      valid, np.unique(gid[valid]).tolist()))
    if not parts:
        return _empty(spans)
    if len(kinds) > 1:
        raise Decline("mixed-codec")
    for sst, _d, js, preps, *_ in parts:
        for j, prep in zip(js, preps):
            why = prep.check_payload(sst, j)
            if why is not None:
                raise Decline(why)
    # sid order = ascending series key: the scan path discovers series
    # in global key order; matching it keeps the group stage's
    # float32 row-sum order aligned with the scan's.
    seen: set[bytes] = set()
    for _sst, d, *_rest, met in parts:
        seen.update(d.skeys[g] for g in met)
    sdir = {sk: i for i, sk in enumerate(sorted(seen))}
    sids = []
    for _sst, d, _js, _preps, gid, _base, _valid, met in parts:
        lut = np.zeros(len(d.skeys), np.int32)
        for g in met:
            lut[g] = sdir[d.skeys[g]]
        sids.append(lut[gid])
    sid = np.concatenate(sids)
    base = np.concatenate([p[5] for p in parts])
    valid = np.concatenate([p[6] for p in parts])
    blocks = [(sst, j, prep) for sst, _d, js, preps, *_ in parts
              for j, prep in zip(js, preps)]
    # Duplicate rows ACROSS generations (a mid-hour checkpoint splits
    # one row-hour over two spills): serveable only when the copies'
    # qualifier-delta ranges are disjoint — then the union the kernel
    # computes IS the overlay. Overlapping ranges could mean a
    # rewrite (newest-wins overlay) => decline to the scan path.
    # Keys are unique within one sstable, so single-generation
    # gathers skip the whole check (and its delta decode).
    if len(spans) > 1:
        rowkey = sid[valid].astype(np.int64) * np.int64(1 << 33) \
            + base[valid]
        rk0 = np.sort(rowkey, kind="stable")
        if (rk0[1:] == rk0[:-1]).any():
            bounds = [prep.delta_bounds(sst, j)
                      for sst, j, prep in blocks]
            rdn = np.concatenate([dn for dn, _ in bounds])[valid]
            rdx = np.concatenate([dx for _, dx in bounds])[valid]
            order = np.lexsort((rdn, rowkey))
            rk = rowkey[order]
            dup_adj = rk[1:] == rk[:-1]
            if (rdx[order][:-1][dup_adj]
                    >= rdn[order][1:][dup_adj]).any():
                raise Decline("duplicate-overlap")
    epoch = int(base[valid].min())
    if int(base[valid].max()) - epoch > 2**31 - MAX_TIMESPAN - 1:
        raise Decline("int32-span")   # rel int32 would wrap
    src = FusedSource()
    src.kind = blocks[0][2].kind
    src.series_keys = list(sdir)
    src.epoch = epoch
    src.spans = spans
    # Group segment map straight from the block keys: no host-side
    # re-partition after the gather. Selector-less gathers get the
    # single implicit group (the executor regroups as it always did).
    groups: dict[tuple, list[int]] = {}
    for sk, i in sdir.items():
        g = group_of(sk)
        if g is not None:
            groups.setdefault(g, []).append(i)
    src.groups = groups
    src.blocks = blocks
    src.rec_off = np.zeros(len(blocks) + 1, np.int64)
    np.cumsum([p.n for _s, _j, p in blocks], out=src.rec_off[1:])
    # Invalid records may lie outside the int32 span of the epoch:
    # they are masked, so their base only has to be representable.
    src.rel_base = np.where(valid, base - epoch, 0).astype(np.int32)
    src.sid = sid
    src.valid = valid
    src.npts = np.concatenate([p.npts for _s, _j, p in blocks])
    src.npoints = int(src.npts.sum())
    src.matched = int(src.npts[valid].sum())
    return src
