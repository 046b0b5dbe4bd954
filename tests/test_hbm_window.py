"""A device window sized to hold the whole history (TSBS cpu-only at 40
hosts x 14 h, every stored point resident: the all-resident deployment
of ISSUE 33 at a size the CPU holds): TSBS's seven 12 h / 8 h types
answered by the resident plan over a score of chunks a metric, the
bytes the window accounts, the boot check of the budget against the
device's memory, the fold-dispatch counter and the refill's span."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import client, store as bench_store, tsbs
from opentsdb_tpu.ops import kernels, oracle
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.storage import devstore
from opentsdb_tpu.storage.devstore import DeviceWindow
from opentsdb_tpu.tools import cli
from opentsdb_tpu.utils import jaxenv
from opentsdb_tpu.utils.config import Config
from tests.test_resident_tracing import serve, stat, walk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "tests", "rehearsal",
                       "tsbs-cpu40-13h.json")) as _f:
    # The 13 h stand-in's 40 hosts x 10 metrics, an hour longer: a 12 h
    # window's start then varies by two hours.
    CFG = dict(json.load(_f), hours=14)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "dash-12h.json")) as _f:
    TYPES = {t["name"]: t for t in json.load(_f)["types"]}
SEED = (1 << 31) + 33
STORED = CFG["hosts"] * len(CFG["metrics"]) * tsbs.loaded_steps(CFG)
# A chunk is cut at the first row-hour that brings the staged points to
# 8,192: 8,280 points (23 series' row-hours) in 16,384 slots. A 12 h
# range over every host is 172,800 points: it meets 21 or 22 chunks.
STAGING = 8192
CHUNK = 8280
FULL, TAIL = divmod(STORED // len(CFG["metrics"]), CHUNK)
assert FULL >= 22 and TAIL
BUDGET = 1 << 21                        # the least power of two >= STORED
EXACT = ("max", "min", "count")
# An avg answer is a float32 sum of 360 float32 values (30 in a 5-min
# bucket) divided by their count, against numpy float64 over the same
# stored values: it reads 1e-6 to 2e-6. The config's f32_rtol, 1e-4,
# leaves that room and still fails the program's own lower precision:
# a bfloat16 wire has 8 bits of mantissa and reads 3.9e-3 (PERF.md §2).
RTOL = 1e-4


@pytest.fixture(scope="module")
def daemon(tmp_path_factory):
    """The store built as the benchmark builds it, then opened as the
    daemon opens it: the budget on the argv, the window refilled from
    the files at boot."""
    wal_dir = str(tmp_path_factory.mktemp("hbm") / "store")
    assert bench_store.build(CFG, SEED, wal_dir)["points"] == STORED
    seen = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cli, "cmd_tsd", lambda args: seen.append(args) or 0)
        # The chunk size is no flag: the default is the largest a budget
        # gets (tools/cli.py), so a smaller default is a smaller chunk.
        mp.setattr(cli, "Config", lambda **kw: Config(
            device_window_staging=STAGING, **kw))
        assert cli.main(["tsd", "--port", "0", "--bind", "127.0.0.1",
                         "--wal", os.path.join(wal_dir, "wal"),
                         "--auto-metric", "--device-window-points",
                         str(BUDGET)]) == 0
        tsdb = cli.make_tsdb(seen[-1])
    yield tsdb
    tsdb.shutdown()


@pytest.fixture(scope="module")
def answers(daemon):
    """One request of each of the seven types, drawn as the benchmark
    draws them, over HTTP; with the counters before and after."""
    rng = tsbs.rng(SEED, 77)
    reqs = {name: client.draw_request(CFG, qtype, rng,
                                      extra="&nocache&trace=1")
            for name, qtype in TYPES.items()}
    before = {n: stat(n) for n in ("devwindow.fold.dispatches",
                                   "devwindow.stage.programs",
                                   "devwindow.stage.miss")}
    misses = daemon.devwindow.window_misses
    got = serve(daemon, *(r.target for r in reqs.values()))
    after = {n: stat(n) for n in before}
    assert daemon.devwindow.window_misses == misses
    return reqs, dict(zip(reqs, got)), before, after


def stage_tags(body: list) -> list[dict]:
    """The resident.stage tags of every sub-query of an answer (a
    sub-query's tree rides on its first result)."""
    return [s["tags"] for r in body if "trace" in r
            for s in walk(r["trace"]) if s["name"] == "resident.stage"]


@pytest.mark.parametrize("qtype", list(TYPES))
def test_every_type_is_resident_and_equals_the_reference(
        daemon, answers, qtype):
    reqs, got, _b, _a = answers
    req, (status, raw) = reqs[qtype], got[qtype]
    assert status == 200
    body = json.loads(raw)
    assert len(body) == req.groups
    assert all(r["rollup"] == "resident" for r in body)
    tags = stage_tags(body)
    assert len(tags) == len(req.ms)
    # The long fold: over every host a 12 h range folds 16 chunks or
    # more of a metric's. A chunk here is 23 of a row-hour's 40
    # series, so the series cut drops whole chunks from a one-host
    # stage (it still folds one for every row-hour of its range, or
    # two) and none from an eight-host one.
    hours = TYPES[qtype]["window_s"] // 3600
    assert hours in (8, 12)
    for t in tags:
        assert t["narrowed"] == (TYPES[qtype]["hosts"] == 1)
        least = hours if t["narrowed"] else 16 * hours // 12
        assert least <= t["chunks"] <= FULL + 1, t
    # Through /q against the benchmark's numpy float64 reference.
    table = tsbs.host_tag_table(CFG, SEED)
    steps = tsbs.loaded_steps(CFG)
    for m_text in req.ms:
        m = tsbs.parse_m(m_text)
        values = tsbs.metric_values(
            CFG, SEED, CFG["metrics"].index(m["metric"]), steps)
        want = tsbs.reference(CFG, table, values, m, req.start, req.end)
        mine = [r for r in body if r["metric"] == m["metric"]]
        assert len(mine) == len(want)
        for r in mine:
            key = tuple((k, r["tags"][k]) for k, v in m["tags"].items()
                        if v == "*" or "|" in v)
            err = tsbs.compare(r["dps"], *want[key],
                               0.0 if m["agg"] in EXACT else RTOL)
            assert err <= (0.0 if m["agg"] in EXACT else RTOL), (
                qtype, m_text, key, err)
    # The executor's answer against ops/oracle.py, host by host.
    m = tsbs.parse_m(req.ms[0])
    interval, dsagg = m["down"]
    hosts = (range(CFG["hosts"]) if m["tags"]["host"] == "*" else
             [int(h[5:]) for h in m["tags"]["host"].split("|")])
    spec = QuerySpec(m["metric"], dict(m["tags"]), m["agg"],
                     downsample=(interval, dsagg))
    out, plan, _c = QueryExecutor(daemon, backend="tpu").run_with_plan(
        spec, req.start, req.end)
    assert plan == "resident"
    ts = CFG["t0"] + CFG["interval_s"] * np.arange(steps, dtype=np.int64)
    keep = (ts >= req.start) & (ts <= req.end)
    vals = tsbs.stored(tsbs.metric_values(
        CFG, SEED, CFG["metrics"].index(m["metric"]), steps))
    interp = "lerp" if Aggregators.get(m["agg"]).interpolates else "none"
    by_host = {r.tags["host"]: r for r in out}
    assert sorted(by_host) == sorted(f"host_{h}" for h in hosts)
    for h in hosts:
        dts, dv = oracle.downsample(ts[keep], vals[keep, h], interval,
                                    dsagg, mode="aligned",
                                    bucket_ts="start")
        wts, wv = oracle.group_aggregate([(dts, dv)], m["agg"],
                                         interp=interp)
        r = by_host[f"host_{h}"]
        np.testing.assert_array_equal(r.timestamps, wts)
        if m["agg"] in EXACT:
            np.testing.assert_array_equal(r.values, wv)
        else:
            np.testing.assert_allclose(r.values, wv, rtol=RTOL)


def test_dispatches_count_the_calls_each_stage_issued(answers):
    reqs, got, before, after = answers
    tags = [t for _st, raw in got.values()
            for t in stage_tags(json.loads(raw))]
    built = [t for t in tags if not t["hit"]]
    assert after["devwindow.stage.miss"] - before[
        "devwindow.stage.miss"] == len(built) == sum(
            len(r.ms) for r in reqs.values())
    calls = sum(t["calls"] for t in built)
    assert after["devwindow.fold.dispatches"] - before[
        "devwindow.fold.dispatches"] == calls
    # A stage's programs: its start, its fold calls, its finish.
    assert after["devwindow.stage.programs"] - before[
        "devwindow.stage.programs"] == calls + 2 * len(built)
    assert all(0 < t["chunks"] <= t["blocks"] <= t["blocks_total"]
               for t in built)
    # A call a group of up to _FOLD_GROUP chunks of one shape class: the
    # full chunks are one class, a metric's tail chunk another.
    group = kernels._FOLD_GROUP
    assert all(-(-t["chunks"] // group) <= t["calls"]
               <= -(-t["chunks"] // group) + 1 for t in built)
    assert calls < sum(t["chunks"] for t in built)


def test_a_dispatch_is_one_chunk_fold_call_a_group(daemon, monkeypatch):
    calls = []
    fold = kernels._chunk_fold

    def counted(chunks, *a, **kw):
        # The chunks a call was handed: a short group's empty places
        # hold its first chunk again.
        calls.append(len({id(c[0]) for c in chunks}))
        return fold(chunks, *a, **kw)
    monkeypatch.setattr(kernels, "_chunk_fold", counted)
    spec = QuerySpec(CFG["metrics"][3], {"host": "host_7"}, "max",
                     downsample=(300, "max"))
    names = ("devwindow.fold.dispatches", "devwindow.stage.programs",
             "devwindow.stage.miss")
    before = [stat(n) for n in names]
    start = CFG["t0"] + 4321
    _out, plan, _c = QueryExecutor(daemon, backend="tpu").run_with_plan(
        spec, start, start + 8 * 3600)
    assert plan == "resident"
    dispatches, programs, built = (stat(n) - b
                                   for n, b in zip(names, before))
    assert built == 1
    assert sum(calls) >= 11
    assert dispatches == len(calls) <= -(-sum(calls) // kernels._FOLD_GROUP) + 1
    assert programs == 2 + len(calls)


def stats_lines(daemon) -> dict:
    (st, body), = serve(daemon, "/stats")
    assert st == 200
    return {ln.split()[0]: float(ln.split()[2])
            for ln in body.decode().splitlines()}


def test_refill_span_says_what_the_restart_loaded(daemon):
    sp = daemon.devwindow_refill
    assert sp["name"] == "devwindow.refill" and sp["ms"] > 0
    assert sp["tags"]["points"] == STORED
    assert sp["tags"]["seconds"] == pytest.approx(sp["ms"] / 1000, abs=1e-3)
    # The refill itself cuts the full chunks; each metric's last points
    # stay staged until a query of the metric asks.
    assert sp["tags"]["chunks"] == FULL * len(CFG["metrics"])
    lines = stats_lines(daemon)
    assert lines["tsd.devwindow.refill.ms"] == sp["ms"]
    assert lines["tsd.devwindow.points.appended"] == STORED
    assert lines["tsd.devwindow.points.evicted"] == 0


def test_stats_carry_the_window_and_the_device(daemon, answers,
                                               monkeypatch):
    dw = daemon.devwindow
    dw.flush()
    lines = stats_lines(daemon)
    chunks = [c for mw in dw._metrics.values() for c in mw.chunks]
    assert len(chunks) == (FULL + 1) * len(CFG["metrics"])
    assert lines["tsd.devwindow.points.resident"] == STORED
    # Every column of every resident chunk, as the device holds it.
    held = sum(c[k].nbytes for c in chunks
               for k in ("ts", "vals", "sid", "valid"))
    assert lines["tsd.devwindow.bytes"] == held
    assert held == sum(c["pad"] for c in chunks) * devstore.SLOT_BYTES
    assert held <= STORED * devstore.POINT_BYTES
    # The CPU's memory_stats() is None: it states no limit, and /stats
    # has no tsd.device.* at all (no share of the host's memory under a
    # name that says the device's).
    assert jaxenv.device_memory() is None
    assert not [n for n in lines if n.startswith("tsd.device.")]
    # A device that states one: its three numbers, asked when /stats is.
    said = {"bytes_limit": 16_909_336_576, "bytes_in_use": held + 7,
            "peak_bytes_in_use": held + 9}
    asked = []
    monkeypatch.setattr(jaxenv, "device_memory",
                        lambda device=None: asked.append(device) or said)
    lines = stats_lines(daemon)
    assert asked == [dw.device]
    assert {n[len("tsd.device."):]: v for n, v in lines.items()
            if n.startswith("tsd.device.")} == said


def test_a_sharded_window_reports_its_fullest_device(monkeypatch):
    """A device runs out alone: of a sharded window /stats has what the
    shards of the fullest device hold, beside that device's memory."""
    import jax

    from opentsdb_tpu.storage.devshard import ShardedDeviceWindow

    devices = jax.local_devices()[:2]
    dw = ShardedDeviceWindow(devices=devices, n_shards=3,
                             staging_points=1000, max_points=1 << 20,
                             background=False)
    ts = 1_700_000_000 + np.arange(0, 3600, 36, dtype=np.int64)
    for s in range(64):                 # 6,400 points over three shards
        dw.append(b"\x00\x00\x01", b"\x00\x00\x01" + s.to_bytes(3, "big"),
                  ts, np.ones(len(ts), np.float32))
    dw.flush()
    by_device = {d: sum(sh._total_bytes for sh in dw._shards
                        if sh.device == d) for d in devices}
    assert all(by_device.values())
    # The first device holds two shards of the three, the second one.
    assert by_device[devices[0]] > by_device[devices[1]]
    fullest = max(by_device, key=by_device.get)
    monkeypatch.setattr(
        jaxenv, "device_memory", lambda device=None: {
            "bytes_limit": 1000 + device.id, "bytes_in_use": 0,
            "peak_bytes_in_use": 0})
    got = {}
    dw.collect_stats(type("Sink", (), {
        "record": lambda self, name, value: got.__setitem__(name, value)})())
    assert got["devwindow.bytes"] == by_device[fullest]
    assert got["devwindow.bytes"] < sum(by_device.values())
    assert got["device.bytes_limit"] == 1000 + fullest.id
    assert got["devwindow.points.resident"] == 6400


def test_a_grid_past_the_reserved_stage_leaves_the_resident_plan(
        daemon, monkeypatch):
    """The boot check keeps room for a stage of STAGE_GRID_MAX cells;
    the resident plan serves no larger one."""
    spec = QuerySpec(CFG["metrics"][2], {"host": "*"}, "avg",
                     downsample=(3600, "avg"))
    start = CFG["t0"] + 1800
    ex = QueryExecutor(daemon, backend="tpu")
    # 40 hosts pad to 64 series, 13 hourly buckets to 16: 1,024 cells.
    monkeypatch.setattr(kernels, "STAGE_GRID_MAX", 1024)
    want, plan, _c = ex.run_with_plan(spec, start, start + 12 * 3600)
    assert plan == "resident"
    monkeypatch.setattr(kernels, "STAGE_GRID_MAX", 1023)
    got, plan, _c = ex.run_with_plan(spec, start, start + 12 * 3600)
    assert plan != "resident"
    assert [r.tags for r in got] == [r.tags for r in want]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.timestamps, w.timestamps)
        np.testing.assert_allclose(g.values, w.values, rtol=RTOL)


def test_bytes_fall_on_eviction_and_on_a_dirty_mark():
    dw = DeviceWindow(staging_points=1000, max_points=5000,
                      background=False)
    key = lambda s: b"\x00\x00\x01" + s.to_bytes(3, "big")

    def held(uid=None):
        return sum(c["bytes"] for u, mw in dw._metrics.items()
                   for c in mw.chunks if uid in (None, u))

    for hour in range(8):               # 8,000 points a metric, by hour
        ts = 1_700_000_000 + hour * 3600 + np.arange(0, 3600, 36,
                                                     dtype=np.int64)
        for uid in (b"\x00\x00\x01", b"\x00\x00\x02"):
            for s in range(10):
                dw.append(uid, key(s), ts, np.ones(len(ts), np.float32))
    dw.flush()
    assert dw.evicted_points == 16000 - dw._total_points > 0
    assert dw._total_points <= 5000
    # 1,000 points pad to 1,024 slots of 13 B.
    assert dw._total_bytes == held() == (
        dw._total_points // 1000 * 1024 * devstore.SLOT_BYTES)
    before = dw._total_bytes
    dw.invalidate(b"\x00\x00\x02")
    assert dw._total_bytes == held() == held(b"\x00\x00\x01") < before
    dw.invalidate()
    assert dw._total_bytes == dw._total_points == 0


class TestBootCheck:
    """tools/cli.py refuses a budget the device cannot hold."""

    STAGE = kernels.stage_accumulator_bytes()

    def boot(self, tmp_path, monkeypatch, limit, *flags):
        seen = []
        monkeypatch.setattr(cli, "cmd_tsd",
                            lambda args: seen.append(args) or 0)
        monkeypatch.setattr(
            jaxenv, "device_memory", lambda device=None: limit and dict(
                bytes_limit=limit, bytes_in_use=0, peak_bytes_in_use=0))
        assert cli.main(["tsd", "--port", "0", "--wal",
                         str(tmp_path / "wal"), "--auto-metric",
                         *flags]) == 0
        return cli.make_tsdb(seen[-1])

    def test_stage_bytes_are_what_the_stage_allocates(self):
        assert self.STAGE == 5 * 4 * ((1 << 24) + 1)
        assert kernels.stage_accumulator_bytes(256) == 5 * 4 * 257

    @pytest.mark.parametrize("points", [1 << 26, 1 << 28])
    def test_a_v5e_holds_the_default_and_four_times_it(
            self, tmp_path, monkeypatch, points):
        v5e = 16_909_336_576            # bytes_limit of a TPU v5 lite
        assert devstore.window_bytes(points, 1 << 20) == (
            points + (2 << 20)) * 26 < v5e
        tsdb = self.boot(tmp_path, monkeypatch, v5e,
                         "--device-window-points", str(points))
        try:
            assert tsdb.devwindow.max_points == points
        finally:
            tsdb.shutdown()

    def test_a_budget_past_the_device_is_refused_with_both_numbers(
            self, tmp_path, monkeypatch):
        v5e = 16_909_336_576
        with pytest.raises(SystemExit) as e:
            self.boot(tmp_path, monkeypatch, v5e,
                      "--device-window-points", str(1 << 30))
        need = devstore.window_bytes(1 << 30, 1 << 20) + self.STAGE
        msg = str(e.value)
        assert f"{need:,} bytes" in msg and f"{v5e:,}" in msg
        assert f"{1 << 30:,} points" in msg
        # The budget it names as the largest that fits does fit, and one
        # chunk more does not.
        fits = int(msg.rsplit(" is ", 1)[1].split()[0].replace(",", ""))
        devstore.require_fits(fits, 1 << 20, self.STAGE, v5e)
        with pytest.raises(ValueError):
            devstore.require_fits(fits + (1 << 20), 1 << 20, self.STAGE,
                                  v5e)

    def test_a_device_that_states_no_limit_is_not_checked(
            self, tmp_path, monkeypatch):
        tsdb = self.boot(tmp_path, monkeypatch, None,
                         "--device-window-points", str(1 << 34))
        try:
            assert tsdb.devwindow.max_points == 1 << 34
        finally:
            tsdb.shutdown()

    @pytest.mark.parametrize("stated", [None, 1 << 26, 1 << 40, 0])
    def test_the_block_cache_is_held_to_what_the_device_has_left(
            self, tmp_path, monkeypatch, stated):
        """The fused plan's block cache beside the window (8 B a
        decoded point): at most half of what the device has left
        beside the default window and a query's stage, which is what
        it gets unstated; the argv can only lower that; 0 turns it
        off."""
        from opentsdb_tpu.compress import devcache
        v5e = 16_909_336_576
        left = v5e - devstore.window_bytes(1 << 26, 1 << 20) - self.STAGE
        most = left // 2 // devcache.POINT_BYTES
        assert 1 << 26 < most < 1 << 40
        flags = () if stated is None else (
            "--device-block-points", str(stated))
        tsdb = self.boot(tmp_path, monkeypatch, v5e, *flags)
        try:
            assert tsdb.config.devblock_points == (
                most if stated is None else min(stated, most))
        finally:
            tsdb.shutdown()

    def test_a_block_cache_is_not_held_where_no_limit_is_stated(
            self, tmp_path, monkeypatch):
        from opentsdb_tpu.utils.config import Config
        tsdb = self.boot(tmp_path, monkeypatch, None)
        try:
            assert tsdb.config.devblock_points \
                == Config().devblock_points == 1 << 23
        finally:
            tsdb.shutdown()
        tsdb = self.boot(tmp_path, monkeypatch, None,
                         "--device-block-points", str(1 << 40))
        try:
            assert tsdb.config.devblock_points == 1 << 40
        finally:
            tsdb.shutdown()
        with pytest.raises(SystemExit):
            self.boot(tmp_path, monkeypatch, None,
                      "--device-block-points", "-1")

    def test_a_sharded_window_is_checked_by_its_fullest_device(
            self, tmp_path, monkeypatch):
        # Eight virtual devices: 1 << 30 points over 8 shards is 1 << 27
        # a device, which a v5e holds; in one shard it is not.
        v5e = 16_909_336_576
        tsdb = self.boot(tmp_path, monkeypatch, v5e,
                         "--device-window-points", str(1 << 30),
                         "--devwindow-shards", "8")
        tsdb.shutdown()
        with pytest.raises(SystemExit):
            self.boot(tmp_path, monkeypatch, v5e,
                      "--device-window-points", str(1 << 30),
                      "--devwindow-shards", "1")
