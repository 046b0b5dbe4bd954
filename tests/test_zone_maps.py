"""Zone maps on the resident chunks: the time and series map a chunk
gets at upload, the blocks chunk_columns picks for a range and for the
series a request matched, the fold that visits only those, and the
counters that say what was visited and how often the series cut it."""

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
# stage declares (query/resident.py, ResidentPlan._stage).
EXACT = {"min", "max", "count"}


def key(s: int) -> bytes:
    return MUID + b"\x00\x00\x01" + s.to_bytes(3, "big")


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 256 slots, so that a test-sized chunk has several."""
    monkeypatch.setattr(devstore, "ZONE_BLOCK", 256)
    return 256


@pytest.fixture
def tiny_blocks(monkeypatch):
    """Blocks of 64 slots, so that a series' run of 360 slots spans a
    few and a live slice of 30 or 60 slots a series spans a block."""
    monkeypatch.setattr(devstore, "ZONE_BLOCK", 64)
    return 64


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


def shuffled_columns(series=12, n=5000, span=4 * HOUR, seed=7, runs=False):
    """One hand-made chunk whose slots are in no order at all (or, with
    ``runs``, in no order of time but sorted by series), as a DevChunks
    with the map and selection the window would give it."""
    rng = np.random.default_rng(seed)
    ts = T0 + rng.integers(0, span, n).astype(np.int64)
    vals = rng.normal(50, 10, n).astype(np.float32)
    sid = rng.integers(0, series, n).astype(np.int32)
    if runs:
        sid.sort()
    pad = devstore._pad_pow2(n)
    zone = devstore._zone_map(ts, sid, pad)

    def padded(a):
        return np.pad(a, (0, pad - n))
    chunk = (padded((ts - T0).astype(np.int32)), padded(vals), padded(sid),
             np.arange(pad) < n)

    def select(start, end):
        return devstore.DevChunks(
            chunks=[chunk], epoch=T0, series_keys=[None] * series,
            generation=0, version=0, block=devstore.ZONE_BLOCK,
            blocks=[zone.select(start, end)], zones=[zone])
    return T0 + span - 1, select


def columns_in(order, **fill):
    """(last timestamp, select(start, end)) of a window filled in
    ``order``, or of the one shuffled chunk."""
    if order == "shuffled":
        return shuffled_columns()
    dw = window()
    fill_in = fill_refill_order if order == "refill" else fill_live_order
    return fill_in(dw, **fill), lambda start, end: dw.chunk_columns(
        MUID, start, end)


def stage(cols, start, end, agg, rate, by_block):
    interval = 300
    qbase = start - start % interval
    sel = dict(blocks=cols.blocks, block=cols.block) if by_block else {}
    return kernels.window_series_stage_chunks(
        cols.chunks, np.int32(start - cols.epoch),
        np.int32(end - cols.epoch), np.int32(qbase - cols.epoch),
        num_series=16, num_buckets=64, interval=interval, agg_down=agg,
        rate=rate, **sel)


def assert_same_stage(cols, start, end, folds=FOLDS, rows=slice(None)):
    """The stage of ``cols``' selection against the whole chunks', on
    ``rows`` (a narrowed selection promises its matched rows alone)."""
    for agg, rate in folds:
        whole = stage(cols, start, end, agg, rate, False)
        blocks = stage(cols, start, end, agg, rate, True)
        for name, a, b in zip(GRIDS, whole, blocks):
            a, b = np.asarray(a)[rows], np.asarray(b)[rows]
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
    last, select = columns_in(order)
    start, end = T0 + HOUR + 700, T0 + 2 * HOUR + 100
    cols = select(start, end)
    total = sum(c[0].shape[0] // small_blocks for c in cols.chunks)
    assert 0 < picked(cols) <= total
    if order != "shuffled":
        # Data clustered in time: most blocks cannot be hit.
        assert len(cols.chunks) > 3 and picked(cols) < total / 2
    assert_same_stage(cols, start, end, [(agg, rate)])


MATCHED = {"one": [7], "three": [2, 3, 9], "all": list(range(12))}


@pytest.mark.parametrize("matched", MATCHED)
@pytest.mark.parametrize("order", ["refill", "live", "shuffled"])
@pytest.mark.parametrize("agg,rate", FOLDS)
def test_narrowed_fold_equals_whole_on_matched_rows(tiny_blocks, order, agg,
                                                    rate, matched):
    last, select = columns_in(order)
    start, end = T0 + HOUR + 700, T0 + 2 * HOUR + 100
    whole = select(start, end)
    sids = np.array(MATCHED[matched])
    cols = whole.narrowed(sids, start, end)
    if matched == "all" or order == "shuffled":
        # Nothing to cut (every series matched, or every block of 64
        # random slots holds some matched series): today's selection.
        assert cols is whole
    else:
        assert cols is not whole
        # Three of twelve series in live slices of 30 slots each still
        # leave two thirds of the blocks.
        assert picked(cols) < picked(whole) * (
            0.75 if (order, matched) == ("live", "three") else 0.5)
    assert_same_stage(cols, start, end, [(agg, rate)], rows=sids)


@pytest.mark.parametrize("runs", [False, True], ids=["scattered", "runs"])
@pytest.mark.parametrize("matched", MATCHED)
def test_selection_is_conservative_on_shuffled_data(tiny_blocks, matched,
                                                    runs):
    """Whatever the order of the slots, a block left out holds no slot
    of a matched series in range. Scattered series cut nothing (every
    block of 64 random slots spans every id); series in runs do, with
    timestamps still in no order."""
    last, select = shuffled_columns(runs=runs)
    start, end = T0 + HOUR + 700, T0 + 2 * HOUR + 100
    sids = np.array(MATCHED[matched])
    whole = select(start, end)
    cols = whole.narrowed(sids, start, end)
    if runs and matched != "all":
        assert 2 * picked(cols) <= picked(whole)
    else:
        assert cols is whole
    rel, _, sid, valid = cols.chunks[0]
    wanted = (valid & np.isin(sid, sids)
              & (rel >= start - T0) & (rel <= end - T0))
    assert wanted.any()
    in_picked = np.zeros(len(rel), bool)
    for b in cols.blocks[0]:
        in_picked[b * cols.block:(b + 1) * cols.block] = True
    assert not (wanted & ~in_picked).any()
    assert_same_stage(cols, start, end, [("max", False), ("avg", False)],
                      rows=sids)


def test_a_block_that_spans_an_hour_boundary_is_picked(tiny_blocks):
    """Ten series in the refill's order: 2,160 points a chunk, so the
    second chunk holds series 6-9 of hour 0, then series 0 and 1 of
    hour 1: its block 22 (slots 1,408-1,471) ends series 9's hour and
    begins series 0's next. Its ids wrap: [smin, smax] = [0, 9]."""
    dw = window()
    fill_refill_order(dw, series=10)
    start, end = T0 + HOUR, T0 + HOUR + 1790
    whole = dw.chunk_columns(MUID, start, end)
    zone = whole.zones[1]
    assert (zone.smin[22], zone.smax[22]) == (0, 9)
    assert (zone.smin[21], zone.smax[21]) == (9, 9)
    for sid in (0, 5):      # 0 begins in it; 5 only might, by the map
        cols = whole.narrowed(np.array([sid]), start, end)
        assert cols is not whole and 22 in cols.blocks[1]
        assert 21 not in cols.blocks[1]
        assert_same_stage(cols, start, end, [("max", False)], rows=[sid])


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
    assert [len(c["zone"].tmin) for c in chunks] == [17] * 5 + [8]
    for c in chunks:
        z = c["zone"]
        assert z.tmin[0] == c["min_ts"] and z.tmax.max() == c["max_ts"]
        assert len(z.smin) == len(z.smax) == len(z.tmax)
    # The first chunk ends 2,913 series into hour 0; the second wraps.
    assert [(int(c["zone"].smin.min()), int(c["zone"].smax.max()))
            for c in chunks[:2]] == [(0, 2912), (0, 3999)]
    # A block holds 182 series' runs of a row-hour, or 183 in part.
    z = chunks[0]["zone"]
    assert ((z.smax - z.smin)[:16] <= 183).all()
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
    # One host of the 4,000 in that window: 180 slots of one row-hour,
    # in one block or two, where the range alone picks 22-23. Eight
    # hosts: sixteen at most.
    span = T0 + 3 * HOUR, T0 + 3 * HOUR + 1790
    assert 22 <= picked(cols) <= 23
    one = cols.narrowed(np.array([1234]), *span)
    assert 1 <= picked(one) <= 2
    eight = cols.narrowed(np.arange(0, 4000, 500), *span)
    assert 8 <= picked(eight) <= 16
    assert cols.narrowed(np.arange(4000), *span) is cols


def test_maps_live_and_die_with_their_chunks(small_blocks):
    dw = window(max_points=9000)
    last = fill_refill_order(dw)
    mw = dw._metrics[MUID]
    # 17,280 points into a budget of 9,000: the oldest chunks went, and
    # every chunk that stayed still has its own map.
    assert dw.evicted_points > 0 and mw.complete_from is not None
    assert all(len(c["zone"].tmin) == len(c["zone"].smax) == 9
               for c in mw.chunks)
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


def add_refill_metric(tsdb, metric, hosts=10, hours=4):
    """A metric written as the boot refill appends it to the window: a
    row-hour a series, hour by hour."""
    rng = np.random.default_rng(17)
    for h in range(hours):
        ts = BASE + h * HOUR + np.arange(0, HOUR, 10, dtype=np.int64)
        for i in range(hosts):
            tsdb.add_batch(metric, ts,
                           rng.normal(50, 10, len(ts)).astype(np.float32),
                           {"host": f"h{i}"})


@pytest.mark.parametrize("shards", [0, 3])
def test_a_new_range_and_a_new_host_compile_nothing(tmp_path, small_blocks,
                                                    shards):
    """Ten hosts in the refill's order with 2,048 points a staged batch:
    six chunks of 2,160 points in 4,096 slots and a last one of 1,440 in
    2,048, so two chunk shape classes. The first request, of every host
    over the whole range, folds every chunk (on the sharded window, on
    every shard's device); after it neither a new range nor a new host
    compiles, whichever blocks of whichever class they pick."""
    tsdb = make_tsdb(tmp_path, hosts=1, device_window_staging=2048,
                     devwindow_shards=shards)
    add_refill_metric(tsdb, "refill.cpu")
    ex = QueryExecutor(tsdb, backend="tpu")
    end = BASE + SPAN - 10

    def spec(*hosts):
        return QuerySpec("refill.cpu", {"host": "|".join(hosts) or "*"},
                         "max", downsample=(300, "max"))
    ex.run(spec(), BASE + 600, end)                      # compiles
    uid = tsdb.metrics.get_id("refill.cpu")
    chunks = [c for w in getattr(tsdb.devwindow, "_shards", [tsdb.devwindow])
              if uid in w._metrics for c in w._metrics[uid].chunks]
    if not shards:
        assert [c["pad"] for c in chunks] == [4096] * 6 + [2048]
    assert len({c["ts"].device for c in chunks}) == max(shards, 1)
    programs = kernels._chunk_fold._cache_size()
    names = ["devwindow.fold.slots.visited",
             "devwindow.fold.stages.narrowed", "devwindow.fold.stages.whole"]
    before = [stat(n) for n in names]
    # Ranges of other lengths (their bucket counts pad to 64 like the
    # first's) and other hosts pick other numbers of blocks in chunks
    # of either class, and run the same programs.
    seen = set()
    for hosts, start in [((), BASE + 4200), ((), BASE), (("h7",), BASE),
                         (("h0", "h9"), BASE + 4500),
                         (("h2", "h5", "h6"), BASE + 2400)]:
        visited = stat(names[0])
        ex.run(spec(*hosts), start, end)
        seen.add(stat(names[0]) - visited)
    assert len(seen) == 5 and min(seen) > 0
    assert [stat(n) - b for n, b in zip(names[1:], before[1:])] == [3, 2]
    assert kernels._chunk_fold._cache_size() == programs


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
                                                       tiny_blocks, shards):
    """Over the plain window and over the sharded one (every shard's
    chunks get the same selection), with the answers held to the scan
    path's."""
    tsdb = make_tsdb(tmp_path, hosts=1, devwindow_shards=shards)
    add_live_metric(tsdb, "live.cpu")
    ex = QueryExecutor(tsdb, backend="tpu")
    end = BASE + SPAN - 10
    names = ["devwindow.fold.slots.visited", "devwindow.fold.slots.skipped",
             "devwindow.stage.miss", "devwindow.fold.stages.narrowed",
             "devwindow.fold.stages.whole"]
    before = {n: stat(n) for n in names}
    spec = QuerySpec("live.cpu", {"host": "*"}, "sum",
                     downsample=(300, "avg"))
    got = [ex.run(spec, start, end) for start in
           (BASE, BASE + 3600, BASE + 3600, BASE + 3 * 3600)]
    # One host of the eight: its slices of 60 points lie in a third of
    # the blocks, so the stage is narrowed (in a shard that holds the
    # host, and to nothing in the others).
    one = QuerySpec("live.cpu", {"host": "h3"}, "sum",
                    downsample=(300, "avg"))
    got_one = [ex.run(one, start, end) for start in
               (BASE, BASE + 3600, BASE + 3600)]
    after = {n: stat(n) for n in names}          # of each, the third: a hit
    visited, skipped, stages, narrowed, whole = (
        after[n] - before[n] for n in names)
    dw = tsdb.devwindow
    uid = tsdb.metrics.get_id("live.cpu")
    resident = sum(c["pad"] for w in getattr(dw, "_shards", [dw])
                   if uid in w._metrics for c in w._metrics[uid].chunks)
    assert (stages, narrowed, whole) == (5, 2, 3)
    assert visited + skipped == resident * stages
    assert 0 < visited < resident * stages and skipped > 0
    tsdb.devwindow = None
    want = ex.run(spec, BASE + 3 * 3600, end)
    assert len(want) == len(got[3]) == 8
    want_one = ex.run(one, BASE + 3600, end)
    assert len(want_one) == len(got_one[2]) == 1
    for a, b in zip(got[3] + got_one[2], want + want_one):
        assert a.tags == b.tags
        np.testing.assert_array_equal(a.timestamps, b.timestamps)
        np.testing.assert_allclose(a.values, b.values, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("first", ["narrow", "wide"])
def test_a_narrowed_stage_serves_its_own_filter_alone(tmp_path, small_blocks,
                                                      first):
    """One host, then every host, of the same metric, range, interval
    and aggregator (and the other way round), the stage cache warm: the
    second never takes the first's stage, each answer is the scan
    path's, and the same filter asked again does hit."""
    tsdb = make_tsdb(tmp_path, hosts=1, device_window_staging=2048)
    add_refill_metric(tsdb, "refill.cpu")
    ex = QueryExecutor(tsdb, backend="tpu")
    start, end = BASE + 1800, BASE + 3 * HOUR
    specs = {"narrow": QuerySpec("refill.cpu", {"host": "h4"}, "sum",
                                 downsample=(300, "avg")),
             "wide": QuerySpec("refill.cpu", {"host": "*"}, "sum",
                               downsample=(300, "avg"))}
    order = [first, "wide" if first == "narrow" else "narrow"]
    names = ["devwindow.stage.hit", "devwindow.stage.miss",
             "devwindow.fold.stages.narrowed", "devwindow.fold.stages.whole"]
    before = [stat(n) for n in names]
    got = {k: ex.run(specs[k], start, end) for k in order}
    assert [stat(n) - b for n, b in zip(names, before)] == [0, 2, 1, 1]
    again = {k: ex.run(specs[k], start, end) for k in order}
    assert [stat(n) - b for n, b in zip(names, before)] == [2, 2, 1, 1]
    tsdb.devwindow = None
    for k, groups in (("narrow", 1), ("wide", 10)):
        want = ex.run(specs[k], start, end)
        assert len(want) == groups
        for res in (got[k], again[k]):
            assert len(res) == groups
            for a, b in zip(res, want):
                assert a.tags == b.tags
                np.testing.assert_array_equal(a.timestamps, b.timestamps)
                np.testing.assert_allclose(a.values, b.values, rtol=1e-5,
                                           atol=1e-4)
