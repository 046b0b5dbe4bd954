"""The per-(series, bucket) stage of the plans that read past the
horizon reduces a stream's runs of equal segment id before it scatters
them (kernels._run_moments over _scatter_runs, the resident fold's
routine): downsample_group and downsample_multigroup against a numpy
float64 segment reduction that shares no code with them, for the orders
and lengths that bend the run reduction, the count of scatter updates
the stage reports, and tsd.query.stage.updates / .slots."""

import json

import jax
import numpy as np
import pytest

from opentsdb_tpu.ops import kernels
from opentsdb_tpu.query import grid as qgrid
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from tests.test_compress import BASE, _int_batch, _mk_tpu_tsdb
from tests.test_fold_runs import AGGS, EXACT, reference
from tests.test_resident_tracing import serve, stat
from tests.test_tsst4_history import daemons, draw      # noqa: F401

TILE, RUNS, BLOCK = (kernels._FOLD_TILE, kernels._FOLD_RUNS,
                     kernels._STAGE_BLOCK)
# name -> (slots a run, the bucket's seconds, buckets, shuffled)
ORDERS = {"hourly": (360, 3600, 4, False), "5min": (30, 300, 16, False),
          "1min": (6, 60, 64, False), "no-order": (360, 3600, 4, True)}
# A test's 16, two steps of the quarter-octave ladder that are not
# whole tiles, and two blocks with a tail.
LENGTHS = [16, 320, 448, 2 * BLOCK + 333]


def series_of(order, n) -> int:
    """The series a stream of ``n`` slots takes for every (series,
    bucket) to come once, as a power of two: 8 at the least."""
    run, _interval, buckets, _ = ORDERS[order]
    return max(8, 1 << (-(-n // (run * buckets)) - 1).bit_length())


def stream(order, n, seed=5, holes=True):
    """(rel_ts, vals, sid, valid) of ``n`` slots laid as a packer lays
    them: a (series, bucket) is ``run`` slots in a row in time order,
    a series' buckets follow each other, the series one another. Some
    slots are invalid mid-run (``holes="range"``: at a series' ends
    alone, as a range cuts them), and the tail is padding."""
    run, interval, buckets, shuffled = ORDERS[order]
    series = series_of(order, n)
    rng = np.random.default_rng(seed)
    i = np.arange(n)
    cell = i // run
    sid = (cell // buckets % series).astype(np.int32)
    rel = ((cell % buckets) * interval
           + (i % run) * (interval // run)).astype(np.int32)
    vals = rng.normal(50, 10, n).astype(np.float32)
    valid = np.ones(n, bool)
    if holes == "range":
        # What a request's range cuts: a series' first and last points.
        valid = ((rel >= 45) & (rel < buckets * interval - 65)
                 & (i < n - n // 20))
    elif holes:
        valid = (rng.random(n) > 0.03) & (i < n - n // 20)
    if shuffled:
        p = rng.permutation(n)
        rel, vals, sid, valid = rel[p], vals[p], sid[p], valid[p]
    return rel, vals, sid, valid


def grid_of(order, n):
    _run, interval, buckets, _ = ORDERS[order]
    return dict(num_series=series_of(order, n), num_buckets=buckets,
                interval=interval)


def check_values(values, mask, cols, order, agg, rate):
    _run, interval, buckets, _ = ORDERS[order]
    series = series_of(order, len(cols[0]))
    want, want_mask = reference(cols, series, buckets, interval,
                                -2**31, 2**31 - 1, agg, rate)
    values, mask = np.asarray(values), np.asarray(mask)
    np.testing.assert_array_equal(mask, want_mask)
    values, want = values[mask], want[mask]
    if agg in EXACT and not rate:
        np.testing.assert_array_equal(values, want.astype(np.float32))
    elif rate:
        np.testing.assert_allclose(values, want, rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_allclose(values, want, rtol=1e-6, atol=1e-6)


def mean_ts(cols, order):
    """downsample_group's series_ts in integers: a bucket's start and
    the floor of the mean offset of its members."""
    _run, interval, buckets, _ = ORDERS[order]
    rel, _v, sid, valid = cols
    series = series_of(order, len(rel))
    seg = sid[valid].astype(np.int64) * buckets + rel[valid] // interval
    off = rel[valid] % interval
    n = np.bincount(seg, minlength=series * buckets)
    total = np.bincount(seg, weights=off, minlength=series * buckets)
    start = np.tile(np.arange(buckets) * interval, series)
    return (start + total // np.maximum(n, 1)).reshape(series, buckets)


@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("order", list(ORDERS))
def test_group_equals_a_numpy_segment_reduction(order, n, agg):
    cols = stream(order, n)
    out = kernels.downsample_group(*cols, **grid_of(order, n), agg_down=agg,
                                   agg_group="sum")
    check_values(out["series_values"], out["series_mask"], cols, order,
                 agg, rate=False)
    # with_ts: the mean member timestamp rides the same turn.
    mask = np.asarray(out["series_mask"])
    assert mask.any()
    np.testing.assert_array_equal(np.asarray(out["series_ts"])[mask],
                                  mean_ts(cols, order)[mask])
    # Never more updates than a slot-wise scatter would be handed, but
    # for the padding: under a tile a block.
    assert 0 < int(out["handed"]) < n + -(-n // BLOCK) * TILE


@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("order", list(ORDERS))
def test_group_with_rate_equals_a_numpy_segment_reduction(order, agg):
    cols = stream(order, 448)
    out = kernels.downsample_group(*cols, **grid_of(order, 448),
                                   agg_down=agg, agg_group="sum", rate=True)
    check_values(out["series_values"], out["series_mask"], cols, order,
                 agg, rate=True)


# The rate is taken from the grid: once a length.
@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("n,rate", [(320, False), (320, True),
                                    (2 * BLOCK + 333, False)])
@pytest.mark.parametrize("order", list(ORDERS))
def test_multigroup_equals_a_numpy_segment_reduction(order, n, rate, agg):
    cols = stream(order, n)
    grid = grid_of(order, n)
    out = kernels.downsample_multigroup(
        *cols, np.arange(grid["num_series"], dtype=np.int32) % 4, **grid,
        num_groups=4, agg_down=agg, agg_group="sum", rate=rate)
    check_values(out["series_values"], out["series_mask"], cols, order,
                 agg, rate)


@pytest.mark.parametrize("agg", AGGS)
def test_a_stream_that_is_all_invalid(agg):
    rel, vals, sid, _valid = stream("hourly", 448)
    for fn, more in ((kernels.downsample_group, {}),
                     (kernels.downsample_multigroup, {"num_groups": 4})):
        args = (rel, vals, sid, np.zeros(448, bool))
        if more:
            args += (np.zeros(8, np.int32),)
        out = fn(*args, **grid_of("hourly", 448), **more, agg_down=agg,
                 agg_group="sum")
        assert not np.asarray(out["series_mask"]).any()
        assert not np.asarray(out["group_mask"]).any()
        # One run a tile, the trash segment's: a turn.
        assert int(out["handed"]) == 4 * RUNS


def moments_both_ways(order, n, seed=9, lead=0):
    """(_segment_moments', _run_moments') statistics of one stream, the
    offsets' sum sixth in both; ``lead`` invalid slots laid before it."""
    _run, interval, buckets, _ = ORDERS[order]
    rel, vals, sid, valid = (np.concatenate([np.zeros(lead, a.dtype), a])
                             for a in stream(order, n, seed=seed,
                                             holes="range"))
    nseg = series_of(order, n) * buckets + 1
    seg = np.where(valid, sid * buckets + rel // interval, nseg - 1)
    extra = (rel % interval).astype(np.float32)
    need = frozenset({"sum", "m2", "min", "max"})
    by_slot = jax.jit(kernels._segment_moments, static_argnums=(3, 4))(
        vals, seg, valid, nseg, need)
    ts_sum = jax.ops.segment_sum(np.where(valid, extra, 0), seg, nseg)
    by_run = jax.jit(kernels._run_moments, static_argnums=(3, 5))(
        vals, seg, valid, nseg, extra, need)
    return ([np.asarray(a) for a in by_slot + (ts_sum,)],
            [np.asarray(a) for a in by_run[:-1]])


@pytest.mark.parametrize("order", list(ORDERS))
def test_the_statistics_are_the_slot_wise_scatters_bits(order):
    """count, min and max in any order; a float32 sum wherever a
    (series, bucket)'s valid slots lie as ONE run of up to _STAGE_FOLD
    (what a range cuts is the run's ends), which
    is then added up a slot after another as the slot-wise scatter
    adds it (XLA:CPU applies a scatter's updates in their order)."""
    (count, total, m2, mn, mx, ts_sum), (
        r_count, r_total, r_m2, r_mn, r_mx, r_ts_sum) = moments_both_ways(
            order, BLOCK + 4321)
    for got, want in ((r_count, count), (r_mn, mn), (r_mx, mx),
                      (r_ts_sum, ts_sum)):    # offsets sum in integers
        np.testing.assert_array_equal(got, want)
    if order == "no-order":
        np.testing.assert_allclose(r_total, total, rtol=1e-5)
        np.testing.assert_allclose(r_m2, m2, rtol=1e-4, atol=1e-3)
    else:
        np.testing.assert_array_equal(r_total, total)
        np.testing.assert_array_equal(r_m2, m2)


@pytest.mark.parametrize("lead", [1, 77, 128, 360, 511, BLOCK - 100])
@pytest.mark.parametrize("order", ["hourly", "5min", "1min"])
def test_a_run_sums_to_the_same_bits_wherever_it_lies(order, lead):
    """The raw plan's packed stream and the fused plan's rows lay one
    series-hour over other tiles, columns and blocks: a run's sum
    follows from its values in their order alone."""
    _, here = moments_both_ways(order, BLOCK + 4321)
    _, there = moments_both_ways(order, BLOCK + 4321, lead=lead)
    for got, want in zip(there, here):
        np.testing.assert_array_equal(got, want)


def test_a_long_run_is_cut_from_its_own_first_slot():
    """A run longer than _STAGE_FOLD slots (a day's bucket of 10 s
    points) is left folds of _STAGE_FOLD slots counted from the run's
    first (a last piece under a tile stays with the one before), added
    in their order: the same bits wherever it lies, and whatever runs
    lie before it."""
    fold = kernels._STAGE_FOLD
    rng = np.random.default_rng(3)
    lengths = [fold, fold + 1, 1, 4 * fold, 360, 2 * fold + TILE, 3,
               fold + TILE - 1, fold - 1, 6 * fold + TILE + 1] + [
        k * fold + tail for k in (2, 3, 5) for tail in (7, 50, TILE - 1)]
    # Values of many sizes: (a + b) + c and a + (b + c) then differ in
    # one sum of three.
    runs = [(rng.normal(0, 1, n) * 10.0 ** rng.integers(-2, 3, n))
            .astype(np.float32) for n in lengths]
    want = []
    for run in runs:
        cuts = list(range(fold, len(run), fold))
        if cuts and len(run) - cuts[-1] < TILE:
            cuts.pop()
        total = np.float32(0)
        for piece in np.split(run, cuts):
            acc = np.float32(0)
            for x in piece:
                acc = np.float32(acc + x)
            total = np.float32(total + acc)
        want.append(total)
    need = frozenset({"sum"})
    nseg = len(runs) + 1
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lengths)])
    moments = jax.jit(kernels._run_moments, static_argnums=(3, 5))
    for lead in (*range(0, 2 * TILE, 13), TILE - 1, TILE, 383, fold - 1,
                 fold, BLOCK - 700, BLOCK - 1):
        valid = np.concatenate([np.zeros(lead, bool), np.ones(len(seg), bool),
                                np.zeros(50, bool)])
        vals = np.zeros(len(valid), np.float32)
        vals[valid] = np.concatenate(runs)
        segs = np.full(len(valid), nseg - 1, np.int32)
        segs[valid] = seg
        count, total, *_ = moments(vals, segs, valid, nseg, None, need)
        np.testing.assert_array_equal(np.asarray(count)[:-1], lengths)
        np.testing.assert_array_equal(np.asarray(total)[:-1],
                                      np.array(want), str(lead))


def expected_updates(seg) -> int:
    """What the stage's scatters are handed for a stream of these
    segment ids: a block's turns are its worst tile's runs over RUNS,
    rounded up, each of tiles x RUNS updates; the stream cut into equal
    blocks of whole tiles, none over BLOCK, and padded to them with a
    run of the trash segment."""
    n = len(seg)
    tile = min(TILE, n)
    blocks = -(-n // BLOCK)
    block = -(-n // (blocks * tile)) * tile
    seg = np.pad(seg, (0, blocks * block - n), constant_values=-1)
    total = 0
    for blk in seg.reshape(-1, block):
        tiles = blk.reshape(-1, tile)
        worst = 1 + (tiles[:, 1:] != tiles[:, :-1]).sum(axis=1).max()
        total += -(-worst // RUNS) * len(tiles) * RUNS
    return total


@pytest.mark.parametrize("n", [448, 3 * BLOCK, 2 * BLOCK + 333])
@pytest.mark.parametrize("order", list(ORDERS))
def test_updates_handed_follow_the_runs(order, n):
    _run, interval, buckets, _ = ORDERS[order]
    rel, vals, sid, valid = stream(order, n, holes=False)
    grid = grid_of(order, n)
    out = kernels.downsample_multigroup(
        rel, vals, sid, valid, np.zeros(grid["num_series"], np.int32),
        **grid, num_groups=1, agg_down="max", agg_group="max")
    handed = int(out["handed"])
    assert handed == expected_updates(sid * buckets + rel // interval)
    if n % BLOCK:
        return
    if order in ("hourly", "5min"):
        # Runs of 360 or 30: eight a tile at most, so a block is one
        # turn of RUNS runs a tile.
        assert handed == n * RUNS // TILE
    elif order == "1min":
        assert handed == 3 * n * RUNS // TILE       # 22 runs a tile
    else:
        # Slots in no order: a run a slot in the worst tile of every
        # block, or nearly; never more than the slots.
        assert n * 7 // 8 <= handed <= n


def test_the_stats_count_raw_and_fused_stages(tmp_path, monkeypatch):
    """/stats after a raw and after a fused request: the slots the
    stages were given and, once read, every update they were handed."""
    t4 = _mk_tpu_tsdb(tmp_path, "s4", "tsst4")
    t0 = _mk_tpu_tsdb(tmp_path, "s0", "none")
    seen = []
    count = qgrid._stage_handed

    def keep(handed, slots):
        seen.append((handed, slots))
        count(handed, slots)
    monkeypatch.setattr(qgrid, "_stage_handed", keep)
    names = ["query.stage.updates", "query.stage.slots"]
    try:
        for t in (t4, t0):
            for host in "abc":
                _int_batch(t, "m.s", host, BASE, 6 * 3600, 10, 5)
            t.checkpoint()
        plans = []
        for t, spec in (
                (t0, QuerySpec("m.s", {"host": "*"}, "sum",
                               downsample=(3600, "avg"))),
                (t0, QuerySpec("m.s", {}, "max", downsample=(300, "max"))),
                (t4, QuerySpec("m.s", {"host": "*"}, "sum",
                               downsample=(3600, "avg"))),
                (t4, QuerySpec("m.s", {"host": "a"}, "max",
                               downsample=(300, "max")))):
            before = [stat(n) for n in names]
            at = len(seen)
            _rows, plan, _ = QueryExecutor(t, backend="tpu").run_with_plan(
                spec, BASE + 100, BASE + 5 * 3600)
            plans.append(plan)
            (handed, slots), = seen[at:]
            updates, given = (stat(n) - b for n, b in zip(names, before))
            assert given == slots >= 3 * 5 * 360
            # Hourly and 5-min runs: a turn a block, RUNS updates a tile.
            assert updates == int(handed) == -(-slots // TILE) * RUNS
            # Read again, nothing new staged: the same total.
            assert stat(names[0]) - before[0] == updates
        assert plans == ["raw", "raw", "fused", "fused"]
    finally:
        t4.shutdown()
        t0.shutdown()


@pytest.mark.parametrize("downsample,ulps", [
    ("1h-avg", 0), ("30m-avg", 0), ("1m-avg", 0),    # a row's run, or less
    ("2h-avg", 16), ("12h-avg", 16), ("90m-avg", 16), ("2h-sum", 16),
    ("4h-dev", 16)])
def test_a_fused_answer_against_the_raw_plans_in_ulps(daemons, downsample,
                                                      ulps):
    """The compressed history (plan ``fused``) against the same points
    stored plain (plan ``raw``), a float32 value against the other's.
    A bucket that is one run of points in both stores is one number:
    no ulp. A bucket of several rows is several runs, which a block of
    the plain store (its rows by series) and a TSST4 block (its records
    by key) do not cut alike: each run's fold added as the turns come
    to them, 9 ulps at the most when this was written (a slot-wise
    scatter made it none); a reordering that loosens it shows here."""
    t4, t0 = daemons
    ask = draw(81)["double-groupby-1"].target.replace(
        "&trace=1", "").replace("1h-avg", downsample)
    ((st4, b4),), ((st0, b0),) = serve(t4, ask), serve(t0, ask)
    got, want = json.loads(b4), json.loads(b0)
    assert (st4, st0) == (200, 200) and len(got) == len(want) > 0
    assert {r["rollup"] for r in got} == {"fused"}
    assert {r["rollup"] for r in want} == {"raw"}
    assert [(r["tags"], list(r["dps"])) for r in got] == [
        (r["tags"], list(r["dps"])) for r in want]
    a, b = (np.array([v for r in body for v in r["dps"].values()],
                     np.float32).view(np.int32).astype(np.int64)
            for body in (got, want))
    assert np.abs(a - b).max() <= ulps
