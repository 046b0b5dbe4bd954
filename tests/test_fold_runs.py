"""window.chunk_fold reduces a block's runs of equal (series, bucket)
before it scatters them (kernels._scatter_runs): the stage it builds
against a numpy float64 segment reduction that shares no code with it,
for the orders and shapes that bend the run reduction, and the count of
scatter updates it reports (tsd.devwindow.fold.updates)."""

import numpy as np
import pytest

from opentsdb_tpu.ops import kernels
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from tests.test_resident_tracing import BASE, SPAN, make_tsdb, stat
from tests.test_zone_maps import T0, columns_in

TILE, RUNS = kernels._FOLD_TILE, kernels._FOLD_RUNS
AGGS = ["sum", "min", "max", "avg", "dev", "count"]
EXACT = {"min", "max", "count"}


def chunk_of(sid, rel, slots, seed=3):
    """One padded chunk of ``slots`` slots holding the given points in
    the given order."""
    n = len(sid)
    vals = np.random.default_rng(seed).normal(50, 10, n).astype(np.float32)
    pad = slots - n
    return (np.pad(np.asarray(rel, np.int32), (0, pad)),
            np.pad(vals, (0, pad)),
            np.pad(np.asarray(sid, np.int32), (0, pad)),
            np.arange(slots) < n)


def a_run_longer_than_a_tile():
    # Series 0 holds 3 tiles and a bit of one 10-minute bucket, then
    # eleven series share what is left of the block, each over the
    # three buckets after it.
    long = 3 * TILE + 5
    rest = 1024 - long
    sid = np.r_[np.zeros(long, int), 1 + np.arange(rest) * 11 // rest]
    rel = np.r_[np.arange(long) % 600, 600 + np.arange(rest) * 37 % 1800]
    return dict(chunk=chunk_of(sid, rel, 1024), block=1024, S=16, B=4,
                interval=600, lo=0, hi=2399)


def many_runs_in_one_tile():
    # The first tile changes segment at every slot (more runs than a
    # turn takes); every other tile of the block is one run.
    sid = np.r_[np.arange(TILE) % 13, 13 + np.arange(2048 - TILE) // TILE]
    rel = np.r_[np.arange(TILE) * 7 % 1200, np.full(2048 - TILE, 30)]
    return dict(chunk=chunk_of(sid, rel, 2048), block=1024, S=32, B=2,
                interval=600, lo=0, hi=1199)


def a_range_that_cuts_a_run():
    # A series is 360 slots, 10 s apart, in two half-hour buckets; the
    # range starts and ends inside a run, so the slots it cuts go to
    # the dump segment mid-run.
    i = np.arange(4 * 360)
    return dict(chunk=chunk_of(i // 360, (i % 360) * 10, 2048), block=1024,
                S=16, B=2, interval=1800, lo=1000, hi=2500)


def a_block_that_is_all_dump():
    # The second block of the chunk is padding, the first out of range
    # but for its last slots: both are visited.
    i = np.arange(900)
    return dict(chunk=chunk_of(i // 300, i * 10, 2048), block=1024,
                S=16, B=2, interval=3600, lo=8900, hi=20000)


def a_chunk_of_1024_slots():
    i = np.arange(1000)
    return dict(chunk=chunk_of(i // 100, (i % 100) * 10, 1024), block=65536,
                S=16, B=4, interval=300, lo=0, hi=999)


def a_grid_of_one_cell():
    i = np.arange(700)
    return dict(chunk=chunk_of(np.zeros(700, int), i, 1024), block=1024,
                S=1, B=1, interval=3600, lo=100, hi=650)


def a_grid_larger_than_the_block():
    # 64 series x 512 buckets = 32,768 cells under blocks of 1,024
    # slots, slots in no order.
    rng = np.random.default_rng(11)
    return dict(chunk=chunk_of(rng.integers(0, 64, 3000),
                               rng.integers(0, 512 * 60, 3000), 4096),
                block=1024, S=64, B=512, interval=60, lo=0, hi=512 * 60)


CASES = [a_run_longer_than_a_tile, many_runs_in_one_tile,
         a_range_that_cuts_a_run, a_block_that_is_all_dump,
         a_chunk_of_1024_slots, a_grid_of_one_cell,
         a_grid_larger_than_the_block]


def reference(chunk, S, B, interval, lo, hi, agg, rate):
    """(series_values, series_mask) of the stage in numpy float64: a
    segment reduction by bincount / ufunc.at, then the rate of a
    bucket against the series' previous nonempty one."""
    rel, vals, sid, valid = chunk
    ok = valid & (rel >= lo) & (rel <= hi)
    seg = (sid[ok].astype(np.int64) * B
           + np.clip(rel[ok] // interval, 0, B - 1))
    v = vals[ok].astype(np.float64)
    count = np.bincount(seg, minlength=S * B).astype(np.float64)
    total = np.bincount(seg, weights=v, minlength=S * B)
    safe = np.maximum(count, 1)
    if agg == "count":
        out = count
    elif agg == "sum":
        out = total
    elif agg == "avg":
        out = total / safe
    elif agg == "dev":
        m2 = np.bincount(seg, weights=(v - (total / safe)[seg]) ** 2,
                         minlength=S * B)
        out = np.sqrt(m2 / safe)
    else:
        out = np.full(S * B, np.inf if agg == "min" else -np.inf)
        (np.minimum if agg == "min" else np.maximum).at(out, seg, v)
    mask = (count > 0).reshape(S, B)
    out = np.where(mask, out.reshape(S, B), 0.0)
    if not rate:
        return out, mask
    rates, ok_rate = np.zeros((S, B)), np.zeros((S, B), bool)
    for s in range(S):
        full = np.flatnonzero(mask[s])
        for prev, b in zip(full[:-1], full[1:]):
            rates[s, b] = (out[s, b] - out[s, prev]) / ((b - prev) * interval)
            ok_rate[s, b] = True
    return rates, ok_rate


@pytest.mark.parametrize("rate", [False, True], ids=["plain", "rate"])
@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_fold_equals_a_numpy_segment_reduction(case, agg, rate):
    c = case()
    chunk, slots = c["chunk"], c["chunk"][0].shape[0]
    blk = min(c["block"], slots)
    got = kernels.window_series_stage_chunks(
        [chunk], np.int32(c["lo"]), np.int32(c["hi"]), np.int32(0),
        num_series=c["S"], num_buckets=c["B"], interval=c["interval"],
        agg_down=agg, rate=rate, blocks=[np.arange(slots // blk)],
        block=c["block"])
    values, mask = np.asarray(got[0]), np.asarray(got[1])
    want, want_mask = reference(chunk, c["S"], c["B"], c["interval"],
                                c["lo"], c["hi"], agg, rate)
    np.testing.assert_array_equal(mask, want_mask)
    assert mask.any() or rate
    # An empty cell's value is the stage's own business (a min's is
    # +inf); its mask says so.
    values, want = values[mask], want[mask]
    if agg in EXACT and not rate:
        np.testing.assert_array_equal(values, want.astype(np.float32))
    elif rate:
        # A rate is a difference of two f32 bucket values over seconds.
        np.testing.assert_allclose(values, want, rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_allclose(values, want, rtol=1e-6, atol=1e-6)
    # Never more updates than a slot-wise scatter would be handed.
    assert 0 < int(got[5]) <= slots


def expected_updates(cols) -> int:
    """What the scatters of a stage over ``cols``' selection are handed:
    a block's turns are its worst tile's runs over RUNS, rounded up,
    each of tiles x RUNS updates."""
    total = 0
    for (rel, _v, sid, valid), ids in zip(cols.chunks, cols.blocks):
        blk = min(cols.block, rel.shape[0])
        tile, runs = min(TILE, blk), min(RUNS, TILE, blk)
        for b in ids:
            at = slice(b * blk, (b + 1) * blk)
            # As the stage below asks: every valid slot in range, one
            # bucket, so a slot's segment is its series or the dump.
            seg = np.where(np.asarray(valid[at]), np.asarray(sid[at]), -1)
            seg = seg.reshape(-1, tile)
            worst = 1 + (seg[:, 1:] != seg[:, :-1]).sum(axis=1).max()
            total += -(-worst // runs) * (blk // tile) * runs
    return total


@pytest.mark.parametrize("order", ["refill", "shuffled"])
def test_updates_handed_follow_the_runs(monkeypatch, order):
    from opentsdb_tpu.storage import devstore
    monkeypatch.setattr(devstore, "ZONE_BLOCK", 1024)
    last, select = columns_in(order)
    cols = select(T0, last)
    got = kernels.window_series_stage_chunks(
        cols.chunks, np.int32(-2**31), np.int32(2**31 - 1), np.int32(0),
        num_series=16, num_buckets=1, interval=2**30, agg_down="max",
        blocks=cols.blocks, block=cols.block)
    slots = sum(len(b) * min(cols.block, c[0].shape[0])
                for c, b in zip(cols.chunks, cols.blocks))
    assert int(got[5]) == expected_updates(cols)
    if order == "refill":
        # A series-hour is 360 slots of one series: a tile holds two
        # runs at most, so a block is one turn of RUNS runs a tile.
        assert int(got[5]) == slots * RUNS // TILE
    else:
        # Slots in no order: a run a slot in the worst tile, or nearly.
        assert int(got[5]) > slots // 2


def test_the_stats_count_the_updates_of_the_stages_built(tmp_path,
                                                         monkeypatch):
    tsdb = make_tsdb(tmp_path, hosts=3)
    ex = QueryExecutor(tsdb, backend="tpu")
    handed = []
    stage = kernels.window_series_stage_chunks

    def keep(*a, **kw):
        grids = stage(*a, **kw)
        handed.append(grids[5])
        return grids
    monkeypatch.setattr(kernels, "window_series_stage_chunks", keep)
    names = ["devwindow.fold.updates", "devwindow.fold.slots.visited"]
    before = [stat(n) for n in names]
    spec = QuerySpec("res.cpu", {"host": "*"}, "max",
                     downsample=(300, "max"))
    for start in (BASE, BASE + 1800):
        ex.run(spec, start, BASE + SPAN - 10)
    updates, visited = (stat(n) - b for n, b in zip(names, before))
    assert len(handed) == 2
    assert 0 < updates == sum(int(h) for h in handed) <= visited
    # Read again, nothing new built: the same total.
    assert stat(names[0]) - before[0] == updates


@pytest.mark.parametrize("tally", ["resident._FOLD_HANDED",
                                   "grid._STAGE_HANDED"])
def test_no_count_is_lost_between_stages_and_readers(monkeypatch, tally):
    """Stages hand their counts over from several threads while others
    read the stats: every count is added once (the resident plan's
    folds, and the stages of the plans that read past the horizon)."""
    import sys
    import threading

    import importlib

    from opentsdb_tpu.query import grid
    monkeypatch.setattr(grid, "_HANDED_MAX", 16)
    module, tally = tally.split(".")
    tally = getattr(importlib.import_module(
        "opentsdb_tpu.query." + module), tally)
    before = tally.total()
    one = np.int32(1)

    def stages():
        for _ in range(400):
            tally.add(one)

    def reader():
        for _ in range(200):
            tally.total()

    threads = [threading.Thread(target=stages) for _ in range(8)] + [
        threading.Thread(target=reader) for _ in range(2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert tally.total() - before == 8 * 400
