"""A resident stage is two small programs and one fold a group of
chunks (kernels.fold_groups): the grids of a stage whose chunks are
folded _FOLD_GROUP a call against the same stage folded one chunk a
call, the device programs a stage issues from Python counted at the
three jitted callables, and the counters that say so
(tsd.devwindow.stage.programs, tsd.devwindow.fold.dispatches)."""

import numpy as np
import pytest

from opentsdb_tpu.ops import kernels
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from tests.test_resident_tracing import BASE, SPAN, make_tsdb, stat
from tests.test_zone_maps import add_refill_metric

K = kernels._FOLD_GROUP
BLOCK = 128
S, B, INTERVAL = 8, 16, 300
GRIDS = ("series_values", "series_mask", "filled", "in_range", "presence")
# (aggregator, rate): a statistic that takes no rounding, the two that
# sum, and a rate over the buckets of one.
FOLDS = [("max", False), ("avg", False), ("dev", False), ("sum", True)]


def chunk(i, slots):
    """Chunk ``i`` of a window in the refill's order: a run a series of
    points seven seconds apart, over two or three buckets, the chunks
    one after another in time; a third of its slots padding."""
    n = slots * 2 // 3
    rng = np.random.default_rng(100 + i)
    at = np.arange(n)
    rel = i * 400 + at % (n // 4) * 7
    pad = slots - n
    return (np.pad(rel.astype(np.int32), (0, pad)),
            np.pad(rng.normal(50, 10, n).astype(np.float32), (0, pad)),
            np.pad((at // (n // 4) + i) % S, (0, pad)).astype(np.int32),
            np.arange(slots) < n)


def picked(c, lo, hi):
    """The blocks of a chunk that hold a valid slot in [lo, hi]: what a
    zone map on time would pick."""
    rel, _v, _s, valid = c
    ok = (valid & (rel >= lo) & (rel <= hi)).reshape(-1, BLOCK)
    return np.flatnonzero(ok.any(axis=1)).astype(np.int32)


def of_one_class(n):
    return [chunk(i, 512) for i in range(n)]


def two_classes_and_a_chunk_out_of_range():
    # Classes of 512 and 256 slots turn about; the fourth chunk lies
    # past the range, so no block of it is picked.
    chunks = [chunk(i, 256 if i % 2 else 512) for i in range(2 * K + 3)]
    rel, *rest = chunks[3]
    chunks[3] = (rel + 1_000_000, *rest)
    return chunks


LAYOUTS = {
    "1": lambda: of_one_class(1),
    "K-1": lambda: of_one_class(K - 1),
    "K": lambda: of_one_class(K),
    "K+1": lambda: of_one_class(K + 1),
    "2K+1": lambda: of_one_class(2 * K + 1),
    "two-classes": two_classes_and_a_chunk_out_of_range,
}
LO, HI = 150, 400 * (2 * K + 3) + 600


def stage(chunks, agg, rate, by_block):
    sel = {}
    if by_block:
        sel = dict(blocks=[picked(c, LO, HI) for c in chunks], block=BLOCK)
    return kernels.window_series_stage_chunks(
        chunks, np.int32(LO), np.int32(HI), np.int32(0), num_series=S,
        num_buckets=B * 4, interval=INTERVAL, agg_down=agg, rate=rate,
        **sel)


@pytest.mark.parametrize("by_block", [True, False],
                         ids=["blocks", "blocks=None"])
@pytest.mark.parametrize("agg,rate", FOLDS)
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_grouped_stage_equals_the_stage_folded_a_chunk_a_call(
        monkeypatch, layout, agg, rate, by_block):
    chunks = LAYOUTS[layout]()
    grouped = stage(chunks, agg, rate, by_block)
    with monkeypatch.context() as mp:
        mp.setattr(kernels, "_FOLD_GROUP", 1)
        single = stage(chunks, agg, rate, by_block)
    assert np.asarray(grouped[1]).any()
    for name, a, b in zip(GRIDS, grouped, single):
        a, b = np.asarray(a), np.asarray(b)
        msg = f"{layout} {agg} rate={rate} {name}"
        if a.dtype == bool or agg == "max":
            np.testing.assert_array_equal(a, b, err_msg=msg)
        elif rate:
            # A rate is a difference of two f32 bucket sums over
            # seconds: the sums' 1e-6 against a difference of any size.
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=2e-5,
                                       err_msg=msg)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                       err_msg=msg)
    # The same blocks visited, the same runs reduced.
    assert int(grouped[5]) == int(single[5]) > 0


def test_a_chunk_with_no_block_picked_is_in_no_call():
    chunks = two_classes_and_a_chunk_out_of_range()
    blocks = [picked(c, LO, HI) for c in chunks]
    assert [i for i, b in enumerate(blocks) if not len(b)] == [3]
    groups = kernels.fold_groups(chunks, blocks, BLOCK)
    # K + 2 chunks of 512 slots (two calls), K chunks of 256 (one, the
    # chunk out of range left out): classes in the order they appear.
    assert [(blk, len(members)) for blk, members in groups] == [
        (BLOCK, K), (BLOCK, 2), (BLOCK, K)]
    assert [m[0][0].shape[0] for _blk, (m, *_rest) in groups] == [
        512, 512, 256]
    handed = [id(c) for _blk, members in groups for c, _p in members]
    assert id(chunks[3]) not in handed and len(set(handed)) == 2 * K + 2
    # Whole chunks, each one block of its own size.
    assert [(blk, len(members)) for blk, members in
            kernels.fold_groups(chunks)] == [
        (512, K), (512, 2), (256, K), (256, 1)]


@pytest.mark.parametrize("agg", ["max", "avg"])
def test_a_block_visited_over_a_range_nothing_lies_in_changes_no_grid(
        monkeypatch, agg):
    """What the sharded window's stages are warmed with
    (ResidentPlan._warm_shards): a block of one chunk of each shape
    class visited over a range with its end before its start is a fold
    call a class, which gives the grids of a stage that folded
    nothing."""
    classes = [chunk(90, 512), chunk(91, 256), chunk(92, 1024)]
    kw = dict(num_series=S, num_buckets=B * 4, interval=INTERVAL,
              agg_down=agg, block=BLOCK)
    calls = counted(monkeypatch)
    warm = kernels.window_series_stage_chunks(
        classes, np.int32(1), np.int32(0), np.int32(0),
        blocks=[(0,)] * len(classes), **kw)
    assert calls.count("_chunk_fold") == len(classes)
    none = np.zeros(0, np.int32)
    del calls[:]
    empty = kernels.window_series_stage_chunks(
        classes, np.int32(LO), np.int32(HI), np.int32(0),
        blocks=[none] * len(classes), **kw)
    assert calls.count("_chunk_fold") == 0
    assert not np.asarray(warm[1]).any()
    for a, b in zip(warm[:5], empty[:5]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_join_lays_the_shards_rows_out_by_an_array():
    """kernels.shard_combine against numpy: rows taken from the shards'
    grids laid end to end, a row past their end zeros and False; the
    same program for another layout of the rows."""
    rng = np.random.default_rng(5)
    H, held = 16, (5, 16, 0, 9)

    def part():
        return (rng.normal(size=(H, B)).astype(np.float32),
                rng.random((H, B)) < 0.5,
                rng.normal(size=(H, B)).astype(np.float32),
                rng.random((H, B)) < 0.5, rng.random(H) < 0.5)
    parts = tuple(part() for _ in held)
    before = None
    for order in (held, held[::-1]):
        rows = np.full(32, len(held) * H, np.int32)
        at = 0
        for n, mine in enumerate(order):
            rows[at:at + mine] = n * H + np.arange(mine)
            at += mine
        got = kernels.shard_combine(parts, rows)
        for g, grids in zip(got, zip(*parts)):
            flat = np.concatenate(grids)
            want = np.zeros((32,) + flat.shape[1:], flat.dtype)
            want[:at] = flat[rows[:at]]
            np.testing.assert_array_equal(np.asarray(g), want)
        assert before in (None, kernels.shard_combine._cache_size())
        before = kernels.shard_combine._cache_size()


def counted(monkeypatch):
    """Every call of the stage's jitted callables, by name: the three
    of a window's stage and the join of a sharded window's shards."""
    calls = []
    for name in ("_chunk_stage_start", "_chunk_fold", "_chunk_stage_finish",
                 "shard_combine"):
        def call(*a, _fn=getattr(kernels, name), _name=name, **kw):
            calls.append(_name)
            return _fn(*a, **kw)
        monkeypatch.setattr(kernels, name, call)
    return calls


@pytest.mark.parametrize("layout,folds", [
    ("1", 1), ("K", 1), ("K+1", 2), ("2K+1", 3), ("two-classes", 3)])
def test_a_stage_is_a_start_its_fold_calls_and_a_finish(monkeypatch,
                                                        layout, folds):
    chunks = LAYOUTS[layout]()
    calls = counted(monkeypatch)
    stage(chunks, "avg", False, True)
    assert calls == (["_chunk_stage_start"] + ["_chunk_fold"] * folds
                     + ["_chunk_stage_finish"])


def test_a_short_group_is_the_program_of_a_full_one():
    """One chunk, K - 1 and K of a class run one program: the vector's
    length and the operands follow the class, not the group."""
    for n in (K, 1, K - 1, 2 * K + 1):
        stage(of_one_class(n), "count", False, True)
        if n == K:
            programs = kernels._chunk_fold._cache_size()
    assert kernels._chunk_fold._cache_size() == programs


@pytest.mark.parametrize("shards", [0, 3])
def test_the_counters_say_what_a_served_stage_issued(tmp_path, monkeypatch,
                                                     shards):
    """Ten hosts in the refill's order, 512 points a staged batch: a
    score of chunks a shard's window holds, so a stage over all of
    them is several calls."""
    tsdb = make_tsdb(tmp_path, hosts=1, device_window_staging=512,
                     devwindow_shards=shards)
    add_refill_metric(tsdb, "refill.cpu")
    ex = QueryExecutor(tsdb, backend="tpu")
    spec = QuerySpec("refill.cpu", {"host": "*"}, "max",
                     downsample=(300, "max"))
    # Before the first stage of a kind a sharded window's devices are
    # warmed with a stage each, built and thrown away
    # (ResidentPlan._warm_shards); the counters say what a served stage's own
    # selection issues, so another range goes first.
    ex.run_with_plan(spec, BASE + 1200, BASE + SPAN - 10)
    calls = counted(monkeypatch)
    names = ["devwindow.stage.programs", "devwindow.fold.dispatches",
             "devwindow.stage.miss"]
    before = [stat(n) for n in names]
    _out, plan, _c = ex.run_with_plan(spec, BASE + 600, BASE + SPAN - 10)
    assert plan == "resident"
    programs, dispatches, built = (stat(n) - b
                                   for n, b in zip(names, before))
    folds = calls.count("_chunk_fold")
    assert built == 1
    assert dispatches == folds >= 2
    # Its start and its finish a shard, the calls, and one join of a
    # sharded window's shards.
    parts = max(shards, 1)
    assert calls.count("_chunk_stage_start") == parts
    assert calls.count("_chunk_stage_finish") == parts
    assert calls.count("shard_combine") == (shards > 0)
    assert programs == len(calls) == 2 * parts + folds + (shards > 0)
    uid = tsdb.metrics.get_id("refill.cpu")
    chunks = sum(len(w._metrics[uid].chunks)
                 for w in getattr(tsdb.devwindow, "_shards",
                                  [tsdb.devwindow]) if uid in w._metrics)
    assert folds < chunks
