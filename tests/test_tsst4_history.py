"""The history that is stored compressed (ISSUE 42's deployment at a
size the CPU holds): TSBS cpu-only, 40 hosts x 13 h, built as the
benchmark builds it in TSST4 blocks and opened as the daemon opens it,
beside the same points stored plain. The five ``hist-12h`` types, each
past the horizon: served by plan ``fused``, equal to the benchmark's
numpy float64 reference and to what the raw plan answers from the plain
store; the boot refill from columnar blocks equal, array for array, to
the refill that scans rows; a second round of drawn ranges compiling
nothing; the spans and counters the fused plan keeps."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import client, store as bench_store, tsbs
from opentsdb_tpu.compress import kernels as ckernels
from opentsdb_tpu.obs.registry import METRICS
from opentsdb_tpu.ops import kernels
from opentsdb_tpu.tools import cli
from tests.test_resident_tracing import (labels_moved, serve, stat,
                                          walk)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL = os.path.join(ROOT, "benchmarks", "tests", "rehearsal")
with open(os.path.join(REHEARSAL, "tsbs-cpu40-13h-tsst4.json")) as _f:
    CFG4 = json.load(_f)
with open(os.path.join(REHEARSAL, "tsbs-cpu40-13h.json")) as _f:
    CFG0 = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "hist-12h.json")) as _f:
    TYPES = {t["name"]: t for t in json.load(_f)["types"]}
SEED = (1 << 31) + 42
STORED = CFG4["hosts"] * len(CFG4["metrics"]) * tsbs.loaded_steps(CFG4)
EXACT = ("max", "min", "count")
RTOL = 1e-4
FUSED_SPANS = ["fused.gather", "fused.dispatch", "fused.wait",
               "fused.fetch", "fused.results"]
COUNTERS = ("compress.fused.attempt", "compress.fused.served",
            "compress.fused.points", "compress.fused.matched_points",
            "compress.devcache.hit", "compress.devcache.miss",
            "compress.devcache.evict",
            "compress.devcache.uploaded_bytes")


def boot(cfg, wal_dir):
    """The store built as the benchmark builds it, then opened as the
    daemon opens it: the config's own argv."""
    assert bench_store.build(cfg, SEED, wal_dir)["points"] == STORED
    argv = [a.replace("{store}", wal_dir).replace(
        "{qcache}", os.path.join(wal_dir, "qcache"))
        for a in cfg["daemon"]]
    seen = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cli, "cmd_tsd", lambda args: seen.append(args) or 0)
        assert cli.main(argv) == 0
        return cli.make_tsdb(seen[-1])


@pytest.fixture(scope="module")
def daemons(tmp_path_factory):
    root = tmp_path_factory.mktemp("tsst4")
    t4 = boot(CFG4, str(root / "s4"))
    t0 = boot(CFG0, str(root / "s0"))
    yield t4, t0
    t4.shutdown()
    t0.shutdown()


def programs() -> int:
    """Programs the fused plan's kernels have compiled."""
    return sum(f._cache_size() for f in (
        ckernels.slab_fill, ckernels.slab_stage_rows,
        ckernels.slab_stage_sel, ckernels.fused_block_stage,
        kernels.window_moment_apply))


def draw(stream: int) -> dict:
    rng = tsbs.rng(SEED, stream)
    return {name: client.draw_request(CFG4, qtype, rng,
                                      extra="&nocache&trace=1")
            for name, qtype in TYPES.items()}


@pytest.fixture(scope="module")
def answers(daemons):
    """One request of each type, drawn as the benchmark draws them,
    answered by both daemons over HTTP."""
    t4, t0 = daemons
    reqs = draw(77)
    before = {n: stat(n) for n in COUNTERS}
    got4 = serve(t4, *(r.target for r in reqs.values()))
    after = {n: stat(n) for n in before}
    got0 = serve(t0, *(r.target for r in reqs.values()))
    return reqs, dict(zip(reqs, got4)), dict(zip(reqs, got0)), \
        before, after


def test_the_store_is_tsst4_and_a_third_smaller(daemons):
    t4, t0 = daemons
    (s4,), (s0,) = t4.store._ssts, t0.store._ssts
    assert s4.format == 4 and s0.format != 4
    assert os.path.getsize(s4.path) < 0.75 * os.path.getsize(s0.path)
    # Both booted over budget: every point appended, the oldest evicted.
    for t in (t4, t0):
        assert t.devwindow.appended_points == STORED
        assert t.devwindow.horizons() is not None


def test_the_columnar_refill_equals_the_row_scan(daemons):
    """The window a restart builds from TSF32 blocks read as columns
    against the one built row by row from the plain store: the same
    series in the same order, the same chunks, array for array."""
    t4, t0 = daemons
    assert t4.devwindow_refill["tags"]["columnar"] is True
    assert t0.devwindow_refill["tags"]["columnar"] is False
    assert t4.devwindow_refill["tags"]["points"] == STORED
    t4.devwindow.flush()
    t0.devwindow.flush()
    m4, m0 = t4.devwindow._metrics, t0.devwindow._metrics
    assert list(m4) == list(m0) and len(m4) == len(CFG4["metrics"])
    compared = 0
    for uid in m4:
        a, b = m4[uid], m0[uid]
        assert a.keys == b.keys and a.epoch == b.epoch
        assert a.complete_from == b.complete_from
        assert np.array_equal(a.last_ts[:len(a.keys)],
                              b.last_ts[:len(b.keys)])
        assert len(a.chunks) == len(b.chunks) > 0
        for ca, cb in zip(a.chunks, b.chunks):
            assert ca["n"] == cb["n"] and ca["max_ts"] == cb["max_ts"]
            for col in ("ts", "vals", "sid"):
                assert np.array_equal(np.asarray(ca[col]),
                                      np.asarray(cb[col])), (uid, col)
            compared += ca["n"]
    assert compared == sum(mw.device_points for mw in m4.values()) > 0


def test_a_fill_decodes_what_the_host_decodes_and_evicts_in_turn(
        daemons):
    """The refill hands the block cache nothing: it opens at the first
    gather, with the rows its bound allows, and a block's row is then
    what the host's decode of the block gives (the same integers, the
    same float32 bits), also after it was pushed out and decoded
    again; the refill left the blocks parsed for the gather."""
    import jax
    from opentsdb_tpu.compress import codecs, fused
    from opentsdb_tpu.compress.devcache import DeviceBlockCache
    t4, _t0 = daemons
    (sst,) = t4.store._ssts
    blocks = [j for j in range(sst.block_count)
              if sst.block_header(j)[0] == codecs.TSF32]
    assert set(fused._sst_dir(sst, t4.table).preps) == set(blocks)
    cache = DeviceBlockCache(t4.config.devblock_points)
    assert cache.nbytes == 0

    def rows(qd, vals, slots):
        return jax.device_get((qd[slots], vals[slots]))

    def gather(metric):
        uid = t4.metrics.get_id(CFG4["metrics"][metric])
        return fused.gather(t4.store, t4.table, uid, CFG4["t0"],
                            CFG4["t0"] + 13 * 3600)

    evicted = stat("compress.devcache.evict")
    for metric in (3, 4, 5, 6, 3):
        src = gather(metric)
        assert 3 < len(src.blocks) <= cache.slots or not cache.slots
        qd, vals = cache.stage(src, rows)
        for k, (s, j, prep) in enumerate(src.blocks):
            want_qd, want_vals = codecs.parse_ts_block(
                s.block_header(j)[0], s.block_enc(j)).columns()
            assert np.array_equal(qd[k, :prep.P], want_qd)
            assert np.array_equal(
                vals[k, :prep.P].view(np.uint32),
                want_vals.astype(np.float32).view(np.uint32))
            assert not qd[k, prep.P:].any()
    assert 0 < cache.slots < len(blocks) and len(cache) <= cache.slots
    assert cache.nbytes == cache.slots * cache.P_BLK * 8 \
        <= t4.config.devblock_points * 8
    # Four metrics' blocks do not fit: the first metric's were pushed
    # out by the fourth's and decoded again.
    assert stat("compress.devcache.evict") > evicted


def test_a_gather_the_cache_cannot_hold_is_declined_to_the_raw_plan(
        daemons, monkeypatch):
    """A fleet-wide gather of more blocks than the cache has rows: the
    fused plan declines (``oversize``) and the raw plan serves the
    same answer whole, where a byte-stream program once stood in."""
    t4, t0 = daemons
    req = draw(80)["double-groupby-1"]
    (_st, fused_body), = serve(t4, req.target)
    monkeypatch.setattr(t4.config, "devblock_points", 2 * 43008)
    oversize = METRICS.counter("compress.fused.decline",
                               {"reason": "oversize"})
    declined = oversize.value
    (st4, body4), = serve(t4, req.target)
    (st0, body0), = serve(t0, req.target)
    assert st4 == 200 and st0 == 200
    assert oversize.value == declined + 1
    got, want = json.loads(body4), json.loads(body0)
    assert got and all(r["rollup"] == "raw" for r in got)
    assert all(r["rollup"] == "fused" for r in json.loads(fused_body))
    for r4, r0, rf in zip(got, want, json.loads(fused_body)):
        assert (r4["tags"], r4["dps"]) == (r0["tags"], r0["dps"])
        assert (rf["tags"], rf["dps"]) == (r0["tags"], r0["dps"])
    assert len(got) == len(want) == CFG4["hosts"]


def test_append_rows_cuts_where_a_row_at_a_time_would():
    """A run fed whole is staged and cut exactly as its rows fed one
    by one: the cut falls after the row that fills the batch."""
    from opentsdb_tpu.storage.devstore import DeviceWindow
    rng = np.random.default_rng(5)
    counts = rng.integers(1, 40, 300)
    keys = [b"\x00\x00\x01" + int(i).to_bytes(3, "big")
            for i in range(len(counts))]
    ends = np.cumsum(counts)
    ts = np.concatenate([1000 + np.arange(c) for c in counts])
    vals = rng.normal(size=len(ts))
    windows = []
    for whole in (True, False):
        dw = DeviceWindow(staging_points=512, max_points=1 << 20,
                          background=False)
        if whole:
            for a in range(0, len(counts), 64):
                p0 = int(ends[a - 1]) if a else 0
                p1 = int(ends[min(a + 64, len(counts)) - 1])
                dw.append_rows(b"\x00\x00\x01", keys[a:a + 64],
                               counts[a:a + 64], ts[p0:p1], vals[p0:p1])
        else:
            for i, c in enumerate(counts):
                p0 = int(ends[i] - c)
                dw.append(b"\x00\x00\x01", keys[i], ts[p0:p0 + c],
                          vals[p0:p0 + c])
        dw.flush()
        windows.append(dw._metrics[b"\x00\x00\x01"])
    a, b = windows
    assert [c["n"] for c in a.chunks] == [c["n"] for c in b.chunks]
    assert len(a.chunks) >= 8
    for ca, cb in zip(a.chunks, b.chunks):
        for col in ("ts", "vals", "sid"):
            assert np.array_equal(np.asarray(ca[col]),
                                  np.asarray(cb[col]))


@pytest.mark.parametrize("qtype", list(TYPES))
def test_every_type_is_fused_and_equals_reference_and_raw(
        daemons, answers, qtype):
    reqs, got4, got0, _b, _a = answers
    req = reqs[qtype]
    (st4, raw4), (st0, raw0) = got4[qtype], got0[qtype]
    assert st4 == 200 and st0 == 200
    body4, body0 = json.loads(raw4), json.loads(raw0)
    # (a) every result past the horizon, by the fused plan; the plain
    # store's by the raw plan.
    assert body4 and all(r["rollup"] == "fused" for r in body4)
    assert all(r["rollup"] == "raw" for r in body0)
    # (b) against the benchmark's numpy float64 reference.
    table = tsbs.host_tag_table(CFG4, SEED)
    steps = tsbs.loaded_steps(CFG4)
    for m_text in req.ms:
        m = tsbs.parse_m(m_text)
        values = tsbs.metric_values(
            CFG4, SEED, CFG4["metrics"].index(m["metric"]), steps)
        want = tsbs.reference(CFG4, table, values, m, req.start, req.end)
        mine = [r for r in body4 if r["metric"] == m["metric"]]
        assert len(mine) == len(want) > 0
        limit = 0.0 if m["agg"] in EXACT else RTOL
        for r in mine:
            key = tuple((k, r["tags"][k]) for k, v in m["tags"].items()
                        if v == "*" or "|" in v)
            assert tsbs.compare(r["dps"], *want[key], limit) <= limit, (
                qtype, m_text, key)
    # (c) against the raw plan over the same points stored plain:
    # result for result, the same float32 arithmetic on the same
    # operands in the same order.
    assert len(body4) == len(body0)
    for r4, r0 in zip(body4, body0):
        assert (r4["metric"], r4["tags"], r4["aggregateTags"]) == (
            r0["metric"], r0["tags"], r0["aggregateTags"])
        assert r4["dps"] == r0["dps"], (qtype, r4["tags"])


def test_the_spans_and_counters_of_a_fused_request(answers):
    reqs, got4, _g0, before, after = answers
    subs = sum(len(r.ms) for r in reqs.values())
    assert after["compress.fused.attempt"] \
        - before["compress.fused.attempt"] == subs
    assert after["compress.fused.served"] \
        - before["compress.fused.served"] == subs
    touched = after["compress.fused.points"] \
        - before["compress.fused.points"]
    matched = after["compress.fused.matched_points"] \
        - before["compress.fused.matched_points"]
    # What the questions need, a point each: the selector kept exactly
    # the rows of the hosts asked for.
    assert matched >= sum(r.series_steps for r in reqs.values())
    assert touched >= matched
    legs, filled = set(), 0
    for name, (_st, raw) in got4.items():
        trees = [r["trace"] for r in json.loads(raw) if "trace" in r]
        assert len(trees) == len(reqs[name].ms)
        for tree in trees:
            (pick,) = [s for s in tree["spans"]
                       if s["name"] == "planner.pick"]
            assert pick["tags"]["plan"] == "fused"
            # The window is asked first and declines by its horizon.
            assert pick["spans"][0]["name"] == "resident.columns"
            assert pick["tags"]["miss"] == "horizon"
            kids = pick["spans"][1:]
            assert [s["name"] for s in kids] == FUSED_SPANS
            assert all("cpu_ms" in s for s in kids)
            gather, dispatch = kids[0]["tags"], kids[1]["tags"]
            assert gather["blocks"] > 0
            assert gather["points"] >= gather["matched"] > 0
            assert gather["payload_bytes"] > 0
            assert isinstance(gather["cached"], int)
            legs.add(dispatch["leg"])
            # A sub-query's cold blocks are decoded under its dispatch.
            fills = kids[1].get("spans", [])
            assert [f["name"] for f in fills] in ([], ["fused.fill"])
            for f in fills:
                assert f["tags"]["blocks"] >= f["tags"]["evicted"] >= 0
                filled += f["tags"]["blocks"]
            # No storage scan and no pack under a fused sub-query.
            assert not [s for s in walk(tree) if s["name"] in (
                "scan", "aggregate", "aggregate.pack")]
    # One host of 40 is a few of a block's rows; every host is all.
    assert legs == {"sel", "rows"}
    # The cache is bounded under the history (15 rows for 45 blocks)
    # and filled by requests alone: they found some blocks, decoded
    # the others on the device and pushed older ones out.
    moved = {n: after[n] - before[n] for n in COUNTERS}
    assert moved["compress.devcache.miss"] == filled > 0
    assert moved["compress.devcache.hit"] > 0
    assert 0 < moved["compress.devcache.evict"] \
        <= moved["compress.devcache.miss"]
    assert moved["compress.devcache.uploaded_bytes"] > 0
    # The gauge: whole rows of 43,008 points at 8 B, of every cache
    # alive (each server of this module opened its own).
    held = stat("compress.devcache.bytes")
    assert held > 0 and held % (43008 * 8) == 0


def test_a_second_round_of_drawn_ranges_compiles_nothing(
        daemons, answers):
    t4, _t0 = daemons
    compiled = programs()
    assert compiled > 0
    misses = stat("compress.devcache.miss")
    for stream in (78, 79):
        reqs = draw(stream)
        for (st, raw), req in zip(
                serve(t4, *(r.target for r in reqs.values())),
                reqs.values()):
            assert st == 200
            body = json.loads(raw)
            assert body and all(r["rollup"] == "fused" for r in body)
    # New hosts, new ranges, blocks decoded anew: no new program.
    assert programs() == compiled
    assert stat("compress.devcache.miss") > misses


def test_the_second_fused_answer_takes_the_first_ones_labels(daemons):
    """The same group-by-host request twice from the compressed
    history: byte-equal bodies, equal to the raw plan's over the plain
    store; the first answer builds the plan's labels, the second takes
    them; a series the store gains (spilled by a checkpoint, so the
    gather's directory grows) makes the next request build them again.
    Last in the file: it adds a series to the module's store."""
    t4, t0 = daemons
    hosts = CFG4["hosts"]
    req = draw(81)["double-groupby-1"]
    (m_text,) = req.ms
    metric = tsbs.parse_m(m_text)["metric"]
    ask = req.target.replace("&trace=1", "")
    moved = labels_moved()

    def grow():
        t4.add_batch(metric, np.array([req.start + 30], np.int64),
                     np.array([7.0], np.float32),
                     {"host": "host_new", "region": "nowhere"})
        t4.checkpoint()

    ((st1, b1), d1, (st2, b2), d2, (st3, b3), d3, _, (st4, b4),
     d4) = serve(t4, ask, moved, ask, moved, req.target, moved, grow, ask,
                 moved)
    assert (st1, st2, st3, st4) == (200,) * 4
    assert b1 == b2
    assert d1 == (0, hosts) and d2 == (hosts, 0) and d3 == (hosts, 0)
    assert d4 == (0, hosts + 1)
    out = json.loads(b1)
    assert len(out) == hosts and all(r["rollup"] == "fused" for r in out)
    ((st0, b0),) = serve(t0, ask)
    want = json.loads(b0)
    assert st0 == 200 and all(r["rollup"] == "raw" for r in want)
    # But for the plan's name (and whether the raw plan's fragments
    # were warm) the two bodies are one, byte for byte.
    assert b1.replace(b'"fused"', b'"raw"') == \
        b0.replace(b'"cached": true', b'"cached": false')
    (res,) = [s for s in walk(json.loads(b3)[0]["trace"])
              if s["name"] == "fused.results"]
    assert res["tags"]["results"] == hosts
    grown = json.loads(b4)
    assert len(grown) == hosts + 1
    assert all(r["rollup"] == "fused" for r in grown)
    (new,) = [r for r in grown if r["tags"]["host"] == "host_new"]
    assert list(new["dps"].values()) == [7.0]
    assert [r["dps"] for r in grown if r is not new] == \
        [r["dps"] for r in out]
