"""Vectorized batch codecs: byte cells <-> columnar numpy arrays.

The scalar codec (codec.py) is the semantics oracle; this module is the hot
path. Batch ingest encodes thousands of points per call (one compacted cell
per row-hour, skipping the reference's write-then-compact amplification
entirely), and queries decode compacted cells straight into the arrays the
TPU kernels consume — no per-point Python.

Wire format is identical to codec.py (and the reference): qualifiers are
big-endian uint16 ``(delta << 4) | flags``; int values big-endian two's
complement on the smallest of 1/2/4/8 bytes; floats IEEE754 single (4 B).
"""

from __future__ import annotations

import numpy as np

from opentsdb_tpu.core.codec import Columns
from opentsdb_tpu.core.const import FLAG_BITS, FLAG_FLOAT, LENGTH_MASK
from opentsdb_tpu.core.errors import IllegalDataError
from opentsdb_tpu.utils.nativeext import ext as _EXT

_INT_WIDTH_BOUNDS = (
    (1, -0x80, 0x7F),
    (2, -0x8000, 0x7FFF),
    (4, -0x80000000, 0x7FFFFFFF),
    (8, -0x8000000000000000, 0x7FFFFFFFFFFFFFFF),
)


def int_widths(int_values: np.ndarray) -> np.ndarray:
    """Per-point smallest encoding width (1/2/4/8) for int64 values."""
    w = np.full(int_values.shape, 8, dtype=np.int64)
    for width, lo, hi in _INT_WIDTH_BOUNDS[:3][::-1]:
        w = np.where((int_values >= lo) & (int_values <= hi), width, w)
    return w


def encode_cell(deltas: np.ndarray, float_values: np.ndarray,
                int_values: np.ndarray, is_float: np.ndarray,
                ) -> tuple[bytes, bytes]:
    """Encode one row's points into a compacted (qualifier, value) cell.

    Inputs must be sorted by delta and deduplicated (see ``sort_dedup``).
    Floats are stored on 4 bytes (IEEE754 single), matching the reference's
    telnet ingest (TSDB.java:321-328); ints on their smallest width.
    Returns (qualifier_bytes, value_bytes) — with the trailing 0x00 meta
    byte only for multi-point cells: a 2-byte qualifier means "single data
    point, raw value" on the wire, so single-point cells omit it.
    """
    if len(deltas) == 0:
        raise ValueError("empty cell")
    qs, vs = encode_cells_multi(deltas, float_values, int_values,
                                is_float, np.array([0]))
    return qs[0], vs[0]


def encode_cells_multi(deltas: np.ndarray, float_values: np.ndarray,
                       int_values: np.ndarray, is_float: np.ndarray,
                       row_starts: np.ndarray,
                       ) -> tuple[list[bytes], list[bytes]]:
    """Encode MANY rows' points in one vectorized pass.

    Points must be sorted by row then delta, deduplicated, with
    ``row_starts`` marking each row's first index (ascending, starting at
    0). All qualifier/value bytes are computed in two flat buffers and
    sliced per row — no per-point Python. Returns (qualifiers, values):
    two parallel lists with one entry per row, the trailing meta byte on
    multi-point cells' values.
    """
    n = len(deltas)
    if n == 0:
        raise ValueError("empty batch")
    deltas = np.asarray(deltas, dtype=np.int64)
    if ((deltas < 0) | (deltas >= 3600)).any():
        raise ValueError("time delta out of range in batch")
    is_float = np.asarray(is_float, dtype=bool)
    all_float = bool(is_float.all())
    if all_float:
        # The telnet/collector hot shape: every point a 4-byte float,
        # so the value buffer is just the packed f32 column — no width
        # computation, no offset cumsum, no fancy-index scatter (the
        # scatter alone cost ~0.5 s per 10M points).
        widths = None
        flags = np.int64(FLAG_FLOAT | 0x3)
        quals = ((deltas << FLAG_BITS) | flags).astype(">u2").tobytes()
        vbytes = np.asarray(float_values).astype(">f4").tobytes()
        offsets = None
    else:
        widths = np.where(is_float, 4, int_widths(np.asarray(int_values)))
        flags = np.where(is_float, FLAG_FLOAT | 0x3, widths - 1)
        quals = ((deltas << FLAG_BITS) | flags).astype(">u2").tobytes()

        offsets = np.zeros(n, dtype=np.int64)
        np.cumsum(widths[:-1], out=offsets[1:])
        total = int(offsets[-1] + widths[-1]) if n else 0
        buf = np.zeros(total, dtype=np.uint8)
        if is_float.any():
            fbytes = np.asarray(float_values)[is_float].astype(">f4") \
                .view(np.uint8).reshape(-1, 4)
            pos = offsets[is_float, None] + np.arange(4)
            buf[pos.ravel()] = fbytes.ravel()
        ivals = np.asarray(int_values)
        for width in (1, 2, 4, 8):
            m = (~is_float) & (widths == width)
            if not m.any():
                continue
            wbytes = ivals[m].astype(">i8").view(np.uint8) \
                .reshape(-1, 8)[:, 8 - width:]
            pos = offsets[m, None] + np.arange(width)
            buf[pos.ravel()] = wbytes.ravel()
        vbytes = buf.tobytes()

    row_starts = np.asarray(row_starts, dtype=np.int64)
    row_ends = np.append(row_starts[1:], n)
    if all_float:
        val_starts = row_starts * 4
        val_ends = row_ends * 4
    else:
        val_starts = offsets[row_starts]
        val_ends = np.append(val_starts[1:], total)
    if _EXT is not None:
        return _EXT.slice_cells(
            quals, vbytes,
            np.ascontiguousarray(row_starts).tobytes(),
            np.ascontiguousarray(row_ends).tobytes(),
            np.ascontiguousarray(val_starts, np.int64).tobytes(),
            np.ascontiguousarray(val_ends, np.int64).tobytes())
    # tolist() yields native ints once (indexing numpy scalars per row
    # plus int() casts cost ~2.7 us/row across millions of row-hours);
    # list comprehensions beat an append loop by ~30% on top. Two
    # parallel lists, not tuples: the caller feeds put_many_columnar,
    # and a tuple per row-hour was ~1 us of pure allocation.
    rs, re_ = row_starts.tolist(), row_ends.tolist()
    out_quals = [quals[2 * a:2 * b] for a, b in zip(rs, re_)]
    out_vals = [
        vbytes[va:vb] + b"\x00" if b - a > 1 else vbytes[va:vb]
        for a, b, va, vb in zip(rs, re_, val_starts.tolist(),
                                val_ends.tolist())]
    return out_quals, out_vals


def decode_cell(qual: bytes, value: bytes, base_ts: int) -> Columns:
    """Decode a cell (single-point or compacted) into columnar arrays.

    Thin wrapper over ``decode_cells_flat`` (C=1) so there is exactly one
    decode implementation: same validation (trailing 0x00 meta byte on
    compacted cells, exact value consumption, legacy 8-byte float repair
    on single cells) — the vectorized equivalent of codec.explode_cell +
    cells_to_columns.
    """
    ts, fvals, ivals, is_float, _ = decode_cells_flat(
        [qual], [value], np.asarray([base_ts], np.int64))
    return Columns(ts, fvals, ivals, is_float)



def sort_dedup_multi(group: np.ndarray | None, deltas: np.ndarray,
                     float_values: np.ndarray, int_values: np.ndarray,
                     is_float: np.ndarray):
    """Sort points by (group, delta) and drop duplicate deltas within a
    group; ``group`` None is one group (one row, or one series).

    Equal (delta, type, value) duplicates collapse silently. A group
    holding conflicting values at one delta is dropped WHOLE and named
    in the returned ``bad`` dict (group -> its first conflicting delta):
    the tombstone-or-fsck rule of the compaction merge (reference
    complexCompact :600-679) applied per group, so one series' conflict
    costs a multi-series chunk that series alone. Last-writer order
    within the input is irrelevant because conflicts are errors, not
    overwrites. Returns (group, deltas, floats, ints, is_float, bad);
    arrays come back by reference where nothing had to move.
    """
    d = np.asarray(deltas)
    f = np.asarray(float_values)
    i = np.asarray(int_values)
    isf = np.asarray(is_float)
    g = None if group is None else np.asarray(group)
    bad: dict[int, int] = {}
    if len(d) <= 1:
        return g, d, f, i, isf, bad
    # The collector pattern: batches arrive sorted, and one O(n)
    # monotonicity check beats the O(n log n) sort + gathers it
    # replaces (~8% of sustained batch ingest).
    if g is None:
        same_g = True
        inorder = d[1:] >= d[:-1]
    else:
        same_g = g[1:] == g[:-1]
        inorder = (g[1:] > g[:-1]) | (same_g & (d[1:] >= d[:-1]))
    if not inorder.all():
        order = (np.argsort(d, kind="stable") if g is None
                 else np.lexsort((d, g)))
        d, f, i, isf = d[order], f[order], i[order], isf[order]
        if g is not None:
            g = g[order]
            same_g = g[1:] == g[:-1]
    dup = (d[1:] == d[:-1]) & same_g
    if dup.any():
        same_type = isf[1:] == isf[:-1]
        same_val = np.where(isf[1:], f[1:] == f[:-1], i[1:] == i[:-1])
        conflict = dup & ~(same_type & same_val)
        keep = np.concatenate(([True], ~dup))
        if conflict.any():
            at = np.flatnonzero(conflict) + 1
            if g is None:
                bad[0] = int(d[at[0]])
                keep[:] = False
            else:
                for gi, di in zip(g[at].tolist(), d[at].tolist()):
                    bad.setdefault(gi, di)
                keep &= ~np.isin(g, list(bad))
        d, f, i, isf = d[keep], f[keep], i[keep], isf[keep]
        if g is not None:
            g = g[keep]
    return g, d, f, i, isf, bad


def duplicate_data_error(delta: int) -> IllegalDataError:
    return IllegalDataError(
        f"Found out of order or duplicate data: delta={delta}"
        " -- run an fsck.")


def sort_dedup(deltas: np.ndarray, float_values: np.ndarray,
               int_values: np.ndarray, is_float: np.ndarray,
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Sort one row's points by delta and drop duplicate deltas: the
    one-group case of ``sort_dedup_multi``, raising IllegalDataError
    where that names the group bad."""
    _, d, f, i, isf, bad = sort_dedup_multi(
        None, deltas, float_values, int_values, is_float)
    if bad:
        raise duplicate_data_error(bad[0])
    return d, f, i, isf


def decode_cells_flat(cell_quals: list[bytes], cell_vals: list[bytes],
                      base_ts: np.ndarray):
    """Decode MANY cells (across many rows) in one vectorized pass.

    The per-cell ``decode_cell`` pays fixed numpy overhead per call,
    which dominates scans of compacted single-cell rows; here the whole
    scan's qualifier/value buffers concatenate into two flat arrays and
    every step (flag split, width resolution, offset cumsum, per-width
    value extraction, validation) runs once. Semantics are identical to
    decode_cell per cell — differential-tested.

    Args:
      cell_quals / cell_vals: per-cell byte strings.
      base_ts: [C] int64 row base time per cell.

    Returns (ts, fvals, ivals, is_float, cell_of_point) flat arrays over
    all points, cells in input order, points in qualifier order.
    """
    C = len(cell_quals)
    if C == 0:
        e = np.empty(0, np.int64)
        return e, np.empty(0, np.float64), e.copy(), \
            np.empty(0, bool), e.copy().astype(np.int32)
    nq = np.fromiter((len(q) for q in cell_quals), np.int64, C)
    if ((nq == 0) | (nq % 2 != 0)).any():
        bad = int(nq[(nq == 0) | (nq % 2 != 0)][0])
        raise IllegalDataError(f"invalid qualifier length {bad}")
    npts = nq // 2
    vlens = np.fromiter((len(v) for v in cell_vals), np.int64, C)

    quals = np.frombuffer(b"".join(cell_quals), dtype=">u2") \
        .astype(np.int64)
    cell_of_point = np.repeat(np.arange(C, dtype=np.int32), npts)
    deltas = quals >> FLAG_BITS
    flags = quals & (FLAG_FLOAT | LENGTH_MASK)
    is_float = (flags & FLAG_FLOAT) != 0
    widths = (flags & LENGTH_MASK) + 1

    vbuf = np.frombuffer(b"".join(cell_vals), dtype=np.uint8)
    vstarts = np.zeros(C, np.int64)
    np.cumsum(vlens[:-1], out=vstarts[1:])

    single = npts == 1
    multi = ~single
    first_pt = np.zeros(C, np.int64)
    np.cumsum(npts[:-1], out=first_pt[1:])

    # Single cells: legacy 8-byte float repair (leading 4 zero bytes) and
    # width := value length (pre-compaction flags were unreliable; the
    # value length is the truth, like the reference's RowSeq extractors).
    adj_vstart = vstarts.copy()
    adj_vlen = vlens.copy()
    rep = single & is_float[first_pt] & (widths[first_pt] == 4) \
        & (vlens == 8)
    if rep.any():
        pos = vstarts[rep, None] + np.arange(4)
        lead = vbuf[pos.ravel()].reshape(-1, 4)
        if lead.any():
            ci = int(np.flatnonzero(rep)[int(lead.any(axis=1).argmax())])
            raise IllegalDataError(
                "Corrupted floating point value: "
                f"{cell_vals[ci].hex()}")
        adj_vstart[rep] += 4
        adj_vlen[rep] -= 4
    widths = widths.copy()
    widths[first_pt[single]] = adj_vlen[single]

    # Multi-point (compacted) cells end with the 0x00 meta byte. The
    # zero-length check must come first: a -1 index would read another
    # cell's byte (or raise IndexError on an empty buffer).
    if multi.any():
        if (vlens[multi] == 0).any():
            raise IllegalDataError(
                "compacted value lacks the 0x00 meta byte (future format?)")
        metas = vbuf[vstarts[multi] + vlens[multi] - 1]
        if metas.any():
            raise IllegalDataError(
                "compacted value lacks the 0x00 meta byte (future format?)")

    # Per-point value offsets: global running sum rebased per cell.
    gcum = np.zeros(len(widths) + 1, np.int64)
    np.cumsum(widths, out=gcum[1:])
    offsets = gcum[:-1] - gcum[first_pt][cell_of_point] \
        + adj_vstart[cell_of_point]
    # Single cells can't mismatch: their one width was just set from the
    # value length, so only compacted cells need the consumed check.
    consumed = gcum[first_pt + npts] - gcum[first_pt]
    bad = multi & (consumed != adj_vlen - 1)
    if bad.any():
        i = int(np.flatnonzero(bad)[0])
        raise IllegalDataError(
            f"Corrupted value: couldn't break down into individual "
            f"values (consumed {int(consumed[i])} bytes, but was "
            f"expecting to consume {int(adj_vlen[i] - 1)})")

    n = len(deltas)
    fvals = np.zeros(n, np.float64)
    ivals = np.zeros(n, np.int64)
    fmask = is_float & (widths == 4)
    if fmask.any():
        pos = offsets[fmask, None] + np.arange(4)
        fvals[fmask] = vbuf[pos.ravel()].reshape(-1, 4) \
            .view(">f4").astype(np.float64).ravel()
    dmask = is_float & (widths == 8)
    if dmask.any():
        pos = offsets[dmask, None] + np.arange(8)
        fvals[dmask] = vbuf[pos.ravel()].reshape(-1, 8).view(">f8").ravel()
    if (is_float & ~(widths == 4) & ~(widths == 8)).any():
        raise IllegalDataError("unsupported float width in cell")
    legal_w = ((widths == 1) | (widths == 2) | (widths == 4)
               | (widths == 8))
    bad_int = (~is_float) & ~legal_w
    if bad_int.any():
        raise IllegalDataError(
            f"Invalid integer value length {int(widths[bad_int][0])}")
    for width, dtype in ((1, ">i1"), (2, ">i2"), (4, ">i4"), (8, ">i8")):
        m = (~is_float) & (widths == width)
        if not m.any():
            continue
        pos = offsets[m, None] + np.arange(width)
        ivals[m] = vbuf[pos.ravel()].reshape(-1, width) \
            .view(dtype).astype(np.int64).ravel()
    fvals = np.where(is_float, fvals, ivals.astype(np.float64))
    ts = base_ts[cell_of_point] + deltas
    return ts, fvals, ivals, is_float, cell_of_point
