"""The deployment that one chip cannot hold (ISSUE 44's, at a size the
CPU holds): TSBS cpu-only, 40 hosts x 10 gauges x 13 h, built as the
benchmark builds it and served by a ``tsd`` booted with ``--mesh 4
--devwindow-shards 4`` on four of tier-1's eight virtual CPU devices,
beside the same points served by the one-device window. The five
``hist-12h`` types, each served by plan ``resident``: equal to a numpy
float64 oracle written here (max, min and count exactly, avg within
1e-4) and to what one shard answers (max, min and count byte for byte);
each device holds one shard, the shards' series are disjoint and add up
to the fleet; the spans and counters the sharded stage keeps; and a
sharded daemon boots on a store of TSST4 blocks and reads it back."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks.lib import client, store as bench_store, tsbs
from opentsdb_tpu.ops import kernels
from opentsdb_tpu.tools import cli
from opentsdb_tpu.utils.config import Config
from tests.test_resident_tracing import serve, stat, walk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "tests", "rehearsal",
                       "tsbs-cpu40-mesh4.json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "hist-12h.json")) as _f:
    TYPES = {t["name"]: t for t in json.load(_f)["types"]}
SEED = (1 << 31) + 44
HOSTS, STEP, T0 = CFG["hosts"], CFG["interval_s"], CFG["t0"]
STEPS = tsbs.loaded_steps(CFG)
STORED = HOSTS * len(CFG["metrics"]) * STEPS
SHARD_FLAGS = ["--mesh", "4", "--devwindow-shards", "4"]
# Points a chunk: a shard's ~46,800 points of a metric are cut into six
# chunks or so and the one-device window's 187,200 into 23, so the two
# add a bucket's float32 partial sums in different orders.
CHUNK = 8192
RTOL = 1e-4


def boot(wal_dir, argv, **store):
    """The store as the benchmark builds it (or a copy of one), opened
    as the daemon opens it: the argv, with chunks of CHUNK points."""
    if not os.path.isdir(wal_dir):
        assert bench_store.build(dict(CFG, **store), SEED,
                                 wal_dir)["points"] == STORED
    argv = [a.replace("{store}", wal_dir).replace(
        "{qcache}", os.path.join(wal_dir, "qcache")) for a in argv]
    seen = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cli, "cmd_tsd", lambda args: seen.append(args) or 0)
        mp.setattr(cli, "Config", lambda **kw: Config(
            device_window_staging=CHUNK, **kw))
        assert cli.main(argv) == 0
        return cli.make_tsdb(seen[-1])


@pytest.fixture(scope="module")
def daemons(tmp_path_factory):
    root = tmp_path_factory.mktemp("mesh4")
    assert CFG["daemon"][-4:] == SHARD_FLAGS
    t4 = boot(str(root / "s4"), CFG["daemon"])
    shutil.copytree(root / "s4", root / "s1",
                    ignore=shutil.ignore_patterns("qcache"))
    t1 = boot(str(root / "s1"), CFG["daemon"][:-4])
    yield t4, t1
    t4.shutdown()
    t1.shutdown()


def draw(stream: int) -> dict:
    rng = tsbs.rng(SEED, stream)
    reqs = {name: client.draw_request(CFG, qtype, rng,
                                      extra="&nocache&trace=1")
            for name, qtype in TYPES.items()}
    # The mix asks for max and avg alone: the fleet's hourly min and
    # count over the same 12 h, for the two other exact aggregators.
    wide = reqs["double-groupby-1"]
    for agg, ds in (("min", "1h-min"), ("sum", "1h-count")):
        m = f"{agg}:{ds}:{CFG['metrics'][1]}{{host=*}}"
        reqs[ds] = client.Request(
            ds, wide.target.split("&m=")[0] + "&m=" + m.replace(
                "{", "%7B").replace("}", "%7D")
            + "&json&nocache&trace=1", [m], wide.start, wide.end,
            HOSTS, wide.series_steps, None)
    return reqs


STAGE_COUNTERS = ("devwindow.stage.miss", "devwindow.stage.shards",
                  "mesh.resident.gather.bytes")


@pytest.fixture(scope="module")
def answers(daemons):
    """Two draws of every type, answered over HTTP by the sharded
    daemon and by the one-device one; around the sharded daemon's
    answers, the counters of its stages."""
    t4, t1 = daemons
    reqs = {f"{name}#{i}": r for i in (1, 2)
            for name, r in draw(76 + i).items()}
    before = {n: stat(n) for n in STAGE_COUNTERS}
    got4 = serve(t4, *(r.target for r in reqs.values()))
    after = {n: stat(n) for n in STAGE_COUNTERS}
    got1 = serve(t1, *(r.target for r in reqs.values()))
    return reqs, dict(zip(reqs, got4)), dict(zip(reqs, got1)), \
        before, after


def body(answer):
    status, payload = answer
    assert status == 200, payload[:300]
    return json.loads(payload)


def oracle(values, m_text, start, end):
    """{host: {bucket: value}} of one sub-query in numpy float64 over
    the float32 the daemon stores, a host and a bucket at a time.
    ``values``: [steps, hosts] hundredths. Every sub-query here groups
    by host, so a group is one series."""
    _agg, ds, rest = m_text.split(":", 2)
    span, dsagg = ds.split("-")
    interval = int(span[:-1]) * {"m": 60, "h": 3600}[span[-1]]
    flt = rest[rest.index("{host=") + 6:-1]
    hosts = range(HOSTS) if flt == "*" else [
        int(h[len("host_"):]) for h in flt.split("|")]
    ts = T0 + STEP * np.arange(STEPS, dtype=np.int64)
    inside = (ts >= start) & (ts <= end)
    bucket = ts[inside] - ts[inside] % interval
    fold = {"max": np.max, "min": np.min, "avg": np.mean,
            "count": len}[dsagg]
    out = {}
    for h in hosts:
        v = (values[inside, h] / 100.0).astype(np.float32).astype(
            np.float64)
        out[f"host_{h}"] = {str(int(b)): float(fold(v[bucket == b]))
                            for b in np.unique(bucket)}
    return out


def test_each_device_holds_one_shard_and_the_shards_add_up(daemons):
    t4, t1 = daemons
    dw = t4.devwindow
    assert dw.n_shards == 4
    assert sorted(dw.shard_device_ids()) == sorted(
        set(dw.shard_device_ids())) and None not in dw.shard_device_ids()
    assert dw.max_points == int(CFG["daemon"][CFG["daemon"].index(
        "--device-window-points") + 1])
    assert [s.max_points for s in dw._shards] == [dw.max_points // 4] * 4
    refill = t4.devwindow_refill["tags"]
    assert refill["shards"] == 4 and refill["points"] == STORED
    assert sum(refill["shard_points"]) == STORED
    assert min(refill["shard_points"]) > 0
    assert "shards" not in t1.devwindow_refill["tags"]
    assert sum(dw.shard_resident_points()) <= STORED
    dw.flush()
    assert dw.shard_resident_points() == refill["shard_points"]
    one = t1.devwindow
    for name in CFG["metrics"]:
        uid = t4.metrics.get_id(name)
        cols = dw.chunk_columns(uid, T0, T0 + STEP * STEPS)
        assert cols is not None and None not in cols.shards
        keys = [set(sc.series_keys) for sc in cols.shards]
        assert sum(map(len, keys)) == len(set().union(*keys)) == HOSTS
        for i, sc in enumerate(cols.shards):
            assert all(dw.shard_of(k) == i for k in sc.series_keys)
            # Chunks on the shard's own device, several of them.
            assert len(sc.chunks) >= 3
            assert all(c[0].devices() == {dw._shards[i].device}
                       for c in sc.chunks)
        whole = one.chunk_columns(t1.metrics.get_id(name), T0,
                                  T0 + STEP * STEPS)
        assert set(whole.series_keys) == set().union(*keys)
        assert len(whole.chunks) > max(len(sc.chunks)
                                       for sc in cols.shards)
    # Nothing evicted, every stored point appended, on either daemon.
    for t in (t4, t1):
        assert stat_of(t, "devwindow.points.appended") == STORED
        assert stat_of(t, "devwindow.points.resident") == STORED
        assert stat_of(t, "devwindow.points.evicted") == 0
    assert stat_of(t4, "mesh.resident.shards") == 4
    assert stat_of(t4, "devwindow.bytes") == max(
        s._total_bytes for s in dw._shards)
    assert stat_of(t4, "mesh.resident.bytes") == sum(
        s._total_bytes for s in dw._shards)
    assert 0.25 <= (stat_of(t4, "devwindow.bytes")
                    / stat_of(t4, "mesh.resident.bytes")) < 0.5


def stat_of(tsdb, name):
    from opentsdb_tpu.stats.collector import StatsCollector
    c = StatsCollector("tsd")
    tsdb.collect_stats(c)
    return next(float(ln.split()[2]) for ln in c.lines
                if ln.split()[0] == "tsd." + name)


@pytest.mark.parametrize("name", [f"{t}#{i}" for i in (1, 2) for t in (
    *TYPES, "1h-min", "1h-count")])
def test_sharded_answers_equal_the_oracle_and_one_shard(answers, name):
    reqs, got4, got1, _b, _a = answers
    req = reqs[name]
    sharded, single = body(got4[name]), body(got1[name])
    values = {}
    assert len(sharded) == len(single) == req.groups
    for answer in (sharded, single):
        assert {r["rollup"] for r in answer} == {"resident"}
    for m_text in req.ms:
        metric = m_text.split(":")[2].split("{")[0]
        mi = CFG["metrics"].index(metric)
        if mi not in values:
            values[mi] = tsbs.metric_values(CFG, SEED, mi, STEPS)
        want = oracle(values[mi], m_text, req.start, req.end)
        mine = {r["tags"]["host"]: r["dps"] for r in sharded
                if r["metric"] == metric}
        theirs = {r["tags"]["host"]: r["dps"] for r in single
                  if r["metric"] == metric}
        assert set(mine) == set(theirs) == set(want)
        exact = m_text.split(":")[1].split("-")[1] != "avg"
        for host, dps in want.items():
            assert set(mine[host]) == set(dps)
            if exact:
                # Equal to the float64 of the float32 stored: what a
                # float16 or bfloat16 fold cannot give.
                assert mine[host] == dps, (m_text, host)
                assert mine[host] == theirs[host]
            else:
                a = np.array([mine[host][b] for b in dps])
                w = np.array([dps[b] for b in dps])
                o = np.array([theirs[host][b] for b in dps])
                assert np.abs(a - w).max() <= RTOL * np.abs(w).max()
                assert np.abs(a - o).max() <= RTOL * np.abs(w).max()
                # float32 means of two-decimal values: a bfloat16 fold
                # is off by 4e-3 and more.
                assert np.abs(a - w).max() <= 1e-5 * np.abs(w).max()


def test_the_sharded_stage_keeps_its_spans_and_counters(answers):
    reqs, got4, got1, before, after = answers
    stages = shards = moved = 0
    for name in reqs:
        for tree in trees(got4[name]):
            for st in (n for n in walk(tree)
                       if n["name"] == "resident.stage"):
                if st["tags"]["hit"]:
                    assert not st.get("spans")
                    continue
                stages += 1
                kids = [c["name"] for c in st["spans"]]
                assert kids == ["resident.shard"] * 4 + [
                    "resident.gather"]
                folds, gather = st["spans"][:4], st["spans"][4]
                assert [f["tags"]["shard"] for f in folds] == [0, 1, 2, 3]
                assert len({f["tags"]["device"] for f in folds}) == 4
                assert sum(f["tags"]["series"] for f in folds) == HOSTS
                assert sum(f["tags"]["chunks"] for f in folds) \
                    == st["tags"]["chunks"]
                assert all("cpu_ms" in f and f["ms"] >= 0 for f in folds)
                assert gather["tags"]["shards"] == 4
                # Three of the four shards' rows cross to the combine
                # device, folded or not: a shard's stage runs on its own
                # device whatever was picked there.
                assert gather["tags"]["bytes"] > 0
                assert "cpu_ms" in gather
                assert sum(c["ms"] for c in st["spans"]) <= st["ms"]
                shards += len(folds)
                moved += gather["tags"]["bytes"]
        # The one-device window's stage has no such children.
        for tree in trees(got1[name]):
            assert not any(n["name"] in ("resident.shard",
                                         "resident.gather")
                           for n in walk(tree))
    assert stages > 0
    delta = {n: after[n] - before[n] for n in STAGE_COUNTERS}
    assert delta == {"devwindow.stage.miss": stages,
                     "devwindow.stage.shards": shards,
                     "mesh.resident.gather.bytes": moved}


def trees(answer):
    return [r["trace"] for r in body(answer) if "trace" in r]


def programs() -> int:
    """Programs the sharded stage's callables hold, a device each."""
    return sum(f._cache_size() for f in (
        kernels._chunk_stage_start, kernels._chunk_fold,
        kernels._chunk_stage_finish, kernels.shard_combine,
        kernels.window_moment_apply))


def test_one_host_of_one_metric_compiles_for_every_shard_and_metric(
        daemons):
    """The benchmark warms a type on one host and, after its first
    type, on the first metric alone. A program belongs to one device,
    a one-host panel folds on the shard that holds its host, and a
    metric's series fall to the shards in their own numbers, its chunks
    into their own shape classes: none of that may compile under a
    later request. A kind of request no other test sends (10-min
    buckets over 11 h): after one host of the first metric, other
    hosts, on every shard, and four other metrics compile nothing."""
    t4, _t1 = daemons
    start = T0 + 1800

    def q(metric, host):
        return (f"/q?start={start}&end={start + 39600}&m=max:10m-max:"
                f"{metric}%7Bhost=host_{host}%7D&json&nocache&trace=1")
    names = CFG["metrics"]
    first, = body(serve(t4, q(names[0], 7))[0])
    assert first["rollup"] == "resident"
    before = programs()
    got = serve(t4, *(q(m, h) for m in names[1:5]
                      for h in range(0, HOSTS, 3)))
    assert all(body(a)[0]["rollup"] == "resident" for a in got)
    # The hosts drawn lie on every shard, one shard a request.
    folded = [[n["tags"]["shard"] for n in walk(tree)
               if n["name"] == "resident.shard" and n["tags"]["chunks"]]
              for a in got for tree in trees(a)]
    assert all(len(f) == 1 for f in folded)
    assert {f[0] for f in folded} == {0, 1, 2, 3}
    assert programs() == before


def test_a_sharded_daemon_boots_on_a_tsst4_store(tmp_path, daemons):
    """The boot refill from columnar blocks routes a run's rows by
    shard: the window it builds is, shard by shard, the one the row
    scan built from the plain store, array for array."""
    t4, _t1 = daemons
    tz = boot(str(tmp_path / "z4"),
              CFG["daemon"] + ["--sstable-codec", "tsst4"],
              store={"sstable_codec": "tsst4"})
    try:
        (sst,) = tz.store._ssts
        assert sst.format == 4
        refill = tz.devwindow_refill["tags"]
        assert refill["columnar"] is True
        assert refill["points"] == STORED
        assert refill["shard_points"] \
            == t4.devwindow_refill["tags"]["shard_points"]
        tz.devwindow.flush()
        t4.devwindow.flush()
        compared = 0
        for a, b in zip(tz.devwindow._shards, t4.devwindow._shards):
            assert list(a._metrics) == list(b._metrics)
            for uid in a._metrics:
                ma, mb = a._metrics[uid], b._metrics[uid]
                assert ma.keys == mb.keys and ma.epoch == mb.epoch
                assert len(ma.chunks) == len(mb.chunks) > 0
                for ca, cb in zip(ma.chunks, mb.chunks):
                    assert ca["n"] == cb["n"]
                    for col in ("ts", "vals", "sid"):
                        assert np.array_equal(np.asarray(ca[col]),
                                              np.asarray(cb[col]))
                    compared += ca["n"]
        assert compared == STORED
        # And read back over HTTP: resident, and equal to the oracle.
        req = draw(99)["cpu-max-all-8"]
        answer = body(serve(tz, req.target)[0])
        assert {r["rollup"] for r in answer} == {"resident"}
        assert len(answer) == req.groups
        for m_text in req.ms[:2]:
            metric = m_text.split(":")[2].split("{")[0]
            want = oracle(tsbs.metric_values(
                CFG, SEED, CFG["metrics"].index(metric), STEPS),
                m_text, req.start, req.end)
            assert {r["tags"]["host"]: r["dps"] for r in answer
                    if r["metric"] == metric} == want
    finally:
        tz.shutdown()
