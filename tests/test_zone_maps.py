"""Zone maps on the resident chunks: the min/max map a chunk gets at
upload, the blocks chunk_columns picks for a range, the fold that
visits only those, and the two counters that say what was visited."""

import numpy as np
import pytest

from opentsdb_tpu.ops import kernels
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.storage import devstore
from opentsdb_tpu.storage.devstore import DeviceWindow
from tests.test_resident_tracing import BASE, SPAN, make_tsdb, stat

MUID = b"\x00\x00\x01"
T0 = 1_700_000_000
HOUR = 3600
GRIDS = ("series_values", "series_mask", "filled", "in_range", "presence")
# (aggregator, rate): what the stage folds chunk-wise.
FOLDS = [("sum", False), ("avg", False), ("min", False), ("max", False),
         ("count", False), ("dev", False), ("sum", True)]
# count / min / max take no rounding, so block-wise they are the same
# bytes; the sums reassociate, within the f32 tolerance the sharded
# stage declares (query/executor.py, _dw_sharded_stage).
EXACT = {"min", "max", "count"}


def key(s: int) -> bytes:
    return MUID + b"\x00\x00\x01" + s.to_bytes(3, "big")


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 256 slots, so that a test-sized chunk has several."""
    monkeypatch.setattr(devstore, "ZONE_BLOCK", 256)
    return 256


def window(**kw) -> DeviceWindow:
    return DeviceWindow(**{"staging_points": 2048, "max_points": 1 << 22,
                           "background": False, **kw})


def fill_refill_order(dw, series=12, hours=4, step=10, seed=5):
    """As the boot refill appends: a row-hour a series, hour by hour."""
    rng = np.random.default_rng(seed)
    for h in range(hours):
        ts = T0 + h * HOUR + np.arange(0, HOUR, step, dtype=np.int64)
        for s in range(series):
            dw.append(MUID, key(s), ts,
                      rng.normal(50, 10, len(ts)).astype(np.float32))
    dw.flush()
    return T0 + hours * HOUR - step


def fill_live_order(dw, series=12, hours=4, step=10, slice_s=300, seed=6):
    """As collectors send: slices of five minutes, time-major."""
    rng = np.random.default_rng(seed)
    for t in range(T0, T0 + hours * HOUR, slice_s):
        ts = np.arange(t, t + slice_s, step, dtype=np.int64)
        for s in range(series):
            dw.append(MUID, key(s), ts,
                      rng.normal(50, 10, len(ts)).astype(np.float32))
    dw.flush()
    return T0 + hours * HOUR - step


def shuffled_columns(series=12, n=5000, span=4 * HOUR, seed=7):
    """One hand-made chunk whose slots are in no order at all, as a
    DevChunks with the map and selection the window would give it."""
    rng = np.random.default_rng(seed)
    ts = T0 + rng.integers(0, span, n).astype(np.int64)
    pad = devstore._pad_pow2(n)
    zmin, zmax = devstore._zone_map(ts, pad)

    def padded(a):
        return np.pad(a, (0, pad - n))
    chunk = (padded((ts - T0).astype(np.int32)),
             padded(rng.normal(50, 10, n).astype(np.float32)),
             padded(rng.integers(0, series, n).astype(np.int32)),
             np.arange(pad) < n)

    def select(start, end):
        return devstore.DevChunks(
            chunks=[chunk], epoch=T0, series_keys=[], generation=0,
            version=0, block=devstore.ZONE_BLOCK,
            blocks=[devstore._blocks_in_range(zmin, zmax, start, end)])
    return T0 + span - 1, select


def stage(cols, start, end, agg, rate, by_block):
    interval = 300
    qbase = start - start % interval
    sel = dict(blocks=cols.blocks, block=cols.block) if by_block else {}
    return kernels.window_series_stage_chunks(
        cols.chunks, np.int32(start - cols.epoch),
        np.int32(end - cols.epoch), np.int32(qbase - cols.epoch),
        num_series=16, num_buckets=64, interval=interval, agg_down=agg,
        rate=rate, **sel)


def assert_same_stage(cols, start, end, folds=FOLDS):
    for agg, rate in folds:
        whole = stage(cols, start, end, agg, rate, False)
        blocks = stage(cols, start, end, agg, rate, True)
        for name, a, b in zip(GRIDS, whole, blocks):
            a, b = np.asarray(a), np.asarray(b)
            msg = f"{agg} rate={rate} {name}"
            if a.dtype == bool or (agg in EXACT and not rate):
                np.testing.assert_array_equal(a, b, err_msg=msg)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                           err_msg=msg)


def picked(cols) -> int:
    return sum(len(b) for b in cols.blocks)


@pytest.mark.parametrize("order", ["refill", "live", "shuffled"])
@pytest.mark.parametrize("agg,rate", FOLDS)
def test_blockwise_fold_equals_whole_chunk_fold(small_blocks, order, agg,
                                                rate):
    if order == "shuffled":
        last, select = shuffled_columns()
    else:
        dw = window()
        fill = fill_refill_order if order == "refill" else fill_live_order
        last = fill(dw)

        def select(start, end):
            return dw.chunk_columns(MUID, start, end)
    start, end = T0 + HOUR + 700, T0 + 2 * HOUR + 100
    cols = select(start, end)
    total = sum(c[0].shape[0] // small_blocks for c in cols.chunks)
    assert 0 < picked(cols) <= total
    if order != "shuffled":
        # Data clustered in time: most blocks cannot be hit.
        assert len(cols.chunks) > 3 and picked(cols) < total / 2
    assert_same_stage(cols, start, end, [(agg, rate)])


@pytest.mark.parametrize("case", ["no_block", "last_partial_block",
                                  "everything"])
def test_ranges_at_the_edges(small_blocks, case):
    dw = window()
    # 12 series x 4 h x 360 points = 17,280 points in refill order:
    # eight chunks of 2,160 in 4,096 slots, the last block of each
    # holding 112 valid slots and 144 of padding.
    last = fill_refill_order(dw)
    whole = dw.chunk_columns(MUID, T0, last)
    assert [c[0].shape[0] for c in whole.chunks] == [4096] * 8
    if case == "no_block":
        # Covered (the window is complete since forever) and empty:
        # before the first point, no block's [min, max] reaches there.
        start, end = T0 - 2 * HOUR, T0 - 10
        cols = dw.chunk_columns(MUID, start, end)
        assert picked(cols) == 0
        out = stage(cols, start, end, "sum", False, True)
        assert not np.asarray(out[1]).any()
    elif case == "last_partial_block":
        # The first chunk ends in series 0-5 of hour 0; its last block
        # (id 8: slots 2,048-2,159 valid) holds the end of series 5's
        # hour and nothing else.
        start, end = T0 + HOUR - 600, T0 + HOUR - 10
        cols = dw.chunk_columns(MUID, start, end)
        assert cols.blocks[0][-1] == 8
        assert all(b.max(initial=0) <= 8 for b in cols.blocks)
    else:
        start, end = T0 - HOUR, last + HOUR
        cols = dw.chunk_columns(MUID, start, end)
        # Every block with a valid slot, and no block of padding alone
        # (ids 9-15 of every chunk).
        assert [list(b) for b in cols.blocks] == [list(range(9))] * 8
    assert_same_stage(cols, start, end,
                      [("avg", False), ("max", False), ("dev", False)])


def test_refill_order_gives_six_chunks_and_their_maps():
    """ISSUE 26's reading of the benchmark's deployment, at its size:
    4 h x 4,000 series x 360 points of one metric, appended as the
    refill does, with the staging size and block size a daemon has."""
    dw = DeviceWindow(background=False)
    assert devstore.ZONE_BLOCK == 1 << 16 and dw.staging_points == 1 << 20
    vals = np.zeros(360, np.float32)
    for h in range(4):
        ts = T0 + h * HOUR + np.arange(0, HOUR, 10, dtype=np.int64)
        for s in range(4000):
            dw.append(MUID, key(s), ts, vals)
    dw.flush()
    chunks = dw._metrics[MUID].chunks
    assert [(c["n"], c["pad"]) for c in chunks] == \
        [(1_048_680, 2_097_152)] * 5 + [(516_600, 524_288)]
    hours = [((c["min_ts"] - T0) // HOUR, (c["max_ts"] - T0 + 10) // HOUR)
             for c in chunks]
    assert hours == [(0, 1), (0, 2), (1, 3), (2, 3), (2, 4), (3, 4)]
    # A map entry a block that holds a valid slot: 17 of a big chunk's
    # 32 (the 17th for its last 104 points), all 8 of the small one.
    assert [len(c["zmin"]) for c in chunks] == [17] * 5 + [8]
    for c in chunks:
        assert c["zmin"][0] == c["min_ts"] and c["zmax"].max() == c["max_ts"]
    everything = dw.chunk_columns(MUID, T0, T0 + 4 * HOUR)
    assert [len(b) for b in everything.blocks] == [17] * 5 + [8]
    resident = sum(c["pad"] for c in chunks)
    assert resident == 11_010_048
    # A one-hour window off the hour touches two row-hours of the four.
    cols = dw.chunk_columns(MUID, T0 + HOUR + 1800, T0 + 2 * HOUR + 1790)
    visited = picked(cols) * devstore.ZONE_BLOCK
    assert 2 * 1_440_000 <= visited <= 0.30 * resident
    # A window inside one hour touches that row-hour alone.
    cols = dw.chunk_columns(MUID, T0 + 3 * HOUR, T0 + 3 * HOUR + 1790)
    assert [len(b) for b in cols.blocks][:3] == [0, 0, 0]
    assert 1_440_000 <= picked(cols) * devstore.ZONE_BLOCK \
        <= 0.16 * resident


def test_maps_live_and_die_with_their_chunks(small_blocks):
    dw = window(max_points=9000)
    last = fill_refill_order(dw)
    mw = dw._metrics[MUID]
    # 17,280 points into a budget of 9,000: the oldest chunks went, and
    # every chunk that stayed still has its own map.
    assert dw.evicted_points > 0 and mw.complete_from is not None
    assert all(len(c["zmin"]) == len(c["zmax"]) == 9 for c in mw.chunks)
    start, end = mw.complete_from + 600, last
    cols = dw.chunk_columns(MUID, start, end)
    assert len(cols.blocks) == len(cols.chunks) == len(mw.chunks)
    assert_same_stage(cols, start, end, [("avg", False)])
    assert dw.chunk_columns(MUID, T0, last) is None     # evicted range
    dw.invalidate(MUID)
    assert mw.chunks == [] and dw.chunk_columns(MUID, start, end) is None


def add_live_metric(tsdb, metric, hosts=8, slice_s=600):
    """A metric written as collectors do, in time-major slices."""
    rng = np.random.default_rng(13)
    for t in range(BASE, BASE + SPAN, slice_s):
        ts = np.arange(t, t + slice_s, 10, dtype=np.int64)
        for i in range(hosts):
            tsdb.add_batch(metric, ts,
                           rng.normal(50, 10, len(ts)).astype(np.float32),
                           {"host": f"h{i}"})


def test_a_new_range_compiles_nothing(tmp_path, small_blocks):
    tsdb = make_tsdb(tmp_path, hosts=1)
    add_live_metric(tsdb, "live.cpu")
    ex = QueryExecutor(tsdb, backend="tpu")
    spec = QuerySpec("live.cpu", {}, "max", downsample=(300, "max"))
    end = BASE + SPAN - 10
    ex.run(spec, BASE + 600, end)                        # compiles
    programs = kernels._chunk_fold._cache_size()
    visited = stat("devwindow.fold.slots.visited")
    # Ranges of other lengths (their bucket counts pad to the same 64)
    # pick other numbers of blocks, and run the same programs.
    seen = set()
    for start in (BASE + 4200, BASE + 2400, BASE):
        ex.run(spec, start, end)
        now = stat("devwindow.fold.slots.visited")
        seen.add(now - visited)
        visited = now
    assert len(seen) == 3 and min(seen) > 0
    assert kernels._chunk_fold._cache_size() == programs


@pytest.mark.parametrize("shards", [0, 3])
def test_visited_plus_skipped_is_resident_times_stages(tmp_path,
                                                       small_blocks, shards):
    """Over the plain window and over the sharded one (every shard's
    chunks get the same selection), with the answers held to the scan
    path's."""
    tsdb = make_tsdb(tmp_path, hosts=1, devwindow_shards=shards)
    add_live_metric(tsdb, "live.cpu")
    ex = QueryExecutor(tsdb, backend="tpu")
    end = BASE + SPAN - 10
    names = ["devwindow.fold.slots.visited", "devwindow.fold.slots.skipped",
             "devwindow.stage.miss"]
    before = {n: stat(n) for n in names}
    spec = QuerySpec("live.cpu", {"host": "*"}, "sum",
                     downsample=(300, "avg"))
    got = [ex.run(spec, start, end) for start in
           (BASE, BASE + 3600, BASE + 3600, BASE + 3 * 3600)]
    after = {n: stat(n) for n in names}                # the third: a hit
    visited, skipped, stages = (after[n] - before[n] for n in names)
    dw = tsdb.devwindow
    uid = tsdb.metrics.get_id("live.cpu")
    resident = sum(c["pad"] for w in getattr(dw, "_shards", [dw])
                   if uid in w._metrics for c in w._metrics[uid].chunks)
    assert stages == 3
    assert visited + skipped == resident * stages
    assert 0 < visited < resident * stages and skipped > 0
    tsdb.devwindow = None
    want = ex.run(spec, BASE + 3 * 3600, end)
    assert len(want) == len(got[3]) == 8
    for a, b in zip(got[3], want):
        assert a.tags == b.tags
        np.testing.assert_array_equal(a.timestamps, b.timestamps)
        np.testing.assert_allclose(a.values, b.values, rtol=1e-5, atol=1e-4)
