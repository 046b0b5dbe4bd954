"""The third kind of traffic, ``live``: paced collectors beside
closed-loop dashboards that end at the acknowledged edge.

Unit cases for the edge, the anchored draw, the reference over loaded +
sent steps, the sample (the first request after each move of the
edge), the schedule (against a telnet stand-in that only answers
barriers) and the span a traced run records; the pending cell
``cpu4k.live-1h`` rehearsed on the CPU stand-in, and the controls that
must come out not ``correct``. One case holds the kinds that were there
still: for seed 7 each cell's workers draw what the parent's
``client.py`` drew (``recorded/targets-seed7.json``).
Slow like ``test_rehearsal.py``: seven cases start a daemon.
"""

import hashlib
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import client, layers, tsbs
from benchmarks.tests import rehearsal_cells
from benchmarks.tests.test_rehearsal import (DEVICE_KEYS, LINE_KEYS, bench,
                                             device_metrics)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "cpu4k.live-1h"
# 4 hosts x 2 gauges, one hour loaded: 360 steps from t = 1000.
CFG = {"name": "hand", "hosts": 4, "interval_s": 10, "hours": 1,
       "t0": 1000, "metrics": ["cpu.a", "cpu.b"], "tags": ["host"],
       "guarantees": {"f32_rtol": 1e-4}}
FIRST_TS = 1000 + 10 * 360
# Buckets of one step: an answer that lacks a point lacks a bucket.
EDGE_TYPE = {"name": "last-10m", "agg": "max", "downsample": "10s-max",
             "metrics": 1, "hosts": 1, "window_s": 600, "anchor": "edge"}
FREE_TYPE = {k: v for k, v in EDGE_TYPE.items() if k != "anchor"}


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def rehearsal_json():
    rehearsal_cells.write()


# -- the edge and the anchored draw -----------------------------------------

def test_edge_never_passes_a_step_some_collector_lacks():
    edge = client.Edge(3, FIRST_TS, 10)
    assert edge.read() == (0, FIRST_TS - 10)     # the loaded span's last
    edge.acknowledged(0, 4)
    edge.acknowledged(1, 2)
    assert edge.read() == (0, FIRST_TS - 10)     # collector 2 has none
    edge.acknowledged(2, 3)
    assert edge.read() == (2, FIRST_TS + 10)
    edge.acknowledged(1, 5)
    assert edge.read() == (3, FIRST_TS + 20)
    edge.acknowledged(2, 9)
    assert edge.read() == (4, FIRST_TS + 30)
    assert [s for s, _t in edge.moved] == [0, 2, 3, 4]
    # Step 1 was acknowledged by all when the edge passed it to 2.
    assert edge.moved_at(1) == edge.moved_at(2) <= edge.moved_at(3)
    assert all(a <= b for (_s, a), (_s2, b) in zip(edge.moved,
                                                   edge.moved[1:]))


def test_an_anchored_request_ends_at_the_edge_of_its_draw():
    edge = client.Edge(1, FIRST_TS, 10)
    rng = tsbs.rng(3, 100)
    edge.acknowledged(0, 6)
    a = client.draw_request(CFG, EDGE_TYPE, rng, edge=edge)
    edge.acknowledged(0, 7)
    b = client.draw_request(CFG, EDGE_TYPE, rng, edge=edge)
    assert (a.edge, a.end, a.start) == (6, FIRST_TS + 50, FIRST_TS - 550)
    assert (b.edge, b.end) == (7, FIRST_TS + 60)
    assert f"start={a.start}&end={a.end}&" in a.target
    # A type without an anchor keeps the draw inside the loaded span,
    # edge or no edge, and consumes the stream as it always did.
    free = client.draw_request(CFG, FREE_TYPE, tsbs.rng(3, 100), edge=edge)
    same = client.draw_request(CFG, FREE_TYPE, tsbs.rng(3, 100))
    assert free.edge is None and free.target == same.target
    assert free.end <= FIRST_TS - 10
    with pytest.raises(ValueError, match="keeps none"):
        client.draw_request(CFG, EDGE_TYPE, rng)
    with pytest.raises(ValueError, match="unknown anchor"):
        client.draw_request(CFG, dict(EDGE_TYPE, anchor="now"), rng,
                            edge=edge)


# -- the reference over loaded + sent steps ---------------------------------

def test_sent_steps_continue_the_loaded_ones():
    loaded = tsbs.metric_values(CFG, 11, 0, 360)
    more = tsbs.metric_values(CFG, 11, 0, 360 + 8)
    assert np.array_equal(more[:360], loaded)
    tags = tsbs.host_tag_table(CFG, 11)
    m = tsbs.parse_m("max:1m-max:cpu.a{host=host_2}")
    end = FIRST_TS + 70             # the eighth sent step: t = 4670
    ts, vals = tsbs.reference(CFG, tags, more, m, end - 600, end)[()]
    # The last minute, 4620..4670, is six sent steps and no loaded one.
    assert ts[-1] == 4620
    assert vals[-1] == tsbs.stored(more[-6:, 2]).max()
    # Over the loaded steps alone the same request has no such bucket.
    short = tsbs.reference(CFG, tags, loaded, m, end - 600, end)[()]
    assert short[0][-1] < ts[-1]


def _answer(req, values):
    tags = tsbs.host_tag_table(CFG, 11)
    out = []
    for m_text in req.ms:
        m = tsbs.parse_m(m_text)
        for key, (ts, v) in tsbs.reference(CFG, tags, values, m, req.start,
                                           req.end).items():
            out.append({"metric": m["metric"], "tags": dict(key),
                        "dps": {str(int(t)): float(x)
                                for t, x in zip(ts, v)}})
    return json.dumps(out).encode()


def _checked(lacking: bool):
    """Two anchored requests at edges 6 and 9, answered over loaded +
    sent steps, or (``lacking``) by a daemon whose window missed the
    newest acknowledged step."""
    edge = client.Edge(1, FIRST_TS, 10)
    done = []
    for k in (6, 9):
        edge.acknowledged(0, k)
        time.sleep(0.002)
        req = client.draw_request(CFG, EDGE_TYPE, tsbs.rng(5, k), edge=edge)
        have = tsbs.metric_values(CFG, 11, 0, 360 + k - lacking)
        done.append(client.Done(req, time.time() + 0.05, 50.0, True, "",
                                _answer(req, have), 0))
    checks = bench_run.Checks()
    bench_run.check_answers(CFG, {"check_max": 8}, 11, done, checks, 1e-4,
                            edge)
    return {n: v for n, v, _lim in checks.rows}, checks.ok(), done, edge


def test_answers_are_checked_over_loaded_plus_sent_steps():
    rows, ok, _done, _edge = _checked(lacking=False)
    assert ok and rows["exact_answers_unequal"] == 0
    assert rows["answers_ahead_of_edge"] == 0


def test_an_answer_that_lacks_an_acknowledged_point_fails():
    rows, ok, _done, _edge = _checked(lacking=True)
    assert not ok and rows["exact_answers_unequal"] == 2


def test_a_request_written_before_its_edge_moved_is_counted():
    _rows, _ok, done, edge = _checked(lacking=False)
    done[1].t_wall_end = edge.moved_at(9) - 1.0     # answered too early
    checks = bench_run.Checks()
    bench_run.check_answers(CFG, {"check_max": 8}, 11, done, checks, 1e-4,
                            edge)
    assert dict((n, v) for n, v, _l in checks.rows)[
        "answers_ahead_of_edge"] == 1


def test_the_sample_holds_the_first_request_after_each_move(monkeypatch):
    """Ten requests of one type at each of three edges, 4 checked: the
    longest, and the first written after each move of the edge,
    whatever the seed draws: the ones a point that shows late would be
    missing from."""
    edge = client.Edge(1, FIRST_TS, 10)
    done, first = [], {}
    for k in (1, 2, 3):
        edge.acknowledged(0, k)
        moved = edge.moved_at(k)
        for j in range(10):
            req = client.draw_request(CFG, EDGE_TYPE, tsbs.rng(5, k, j),
                                      edge=edge)
            # Written 0.3 s, 0.4 s ... after the move; listed backwards.
            d = client.Done(
                req, moved + 0.3 + 0.1 * (9 - j) + 0.05, 50.0, True, "",
                _answer(req, tsbs.metric_values(CFG, 11, 0, 360 + k)), j % 2)
            done.append(d)
        first[k] = done[-1]
    seen = []
    real = bench_run.tsbs.compare
    monkeypatch.setattr(bench_run.tsbs, "compare", lambda dps, *a: seen.append(
        dps) or real(dps, *a))
    checks = bench_run.Checks()
    bench_run.check_answers(CFG, {"check_max": 4}, 11, done, checks, 1e-4,
                            edge)
    assert checks.ok() and len(seen) == 4
    for k in (1, 2, 3):
        assert json.loads(first[k].body)[0]["dps"] in seen
    # An answer that lacks its step only there, as after a late drain:
    # the first request after the newest move alone is served without it.
    req = first[3].req
    first[3].body = _answer(req, tsbs.metric_values(CFG, 11, 0, 360 + 2))
    checks = bench_run.Checks()
    bench_run.check_answers(CFG, {"check_max": 4}, 11, done, checks, 1e-4,
                            edge)
    assert dict((n, v) for n, v, _l in checks.rows)[
        "exact_answers_unequal"] == 1


# -- the schedule, against a stand-in that only answers barriers ------------

class Telnet:
    """Accepts connections, counts ``put`` lines, answers ``version``
    after ``delay_s``."""

    def __init__(self, delay_s=0.0):
        self.delay_s, self.puts = delay_s, 0
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        buf = b""
        while True:
            got = conn.recv(65536)
            if not got:
                return
            buf += got
            *lines, buf = buf.split(b"\n")
            for ln in lines:
                if ln.startswith(b"put "):
                    self.puts += 1
                elif ln == b"version":
                    time.sleep(self.delay_s)
                    conn.sendall(b"opentsdb_tpu stand-in\n")


MIX = {"kind": "live", "workers": 1, "collectors": 2, "hosts_per_send": 2,
       "warm_steps": 2, "tail_steps": 1, "pace": 100.0}


def _paced(delay_s, seconds=0.55):
    server = Telnet(delay_s)
    gen = client.IngestLoad(CFG, MIX, 11, 5.0)
    try:
        assert gen.warm(server.port)
        assert gen.edge.read() == (2, FIRST_TS + 10)
        gen.start_paced()
        time.sleep(seconds)
        elapsed = gen.finish_paced()
        assert not any(c.lost for c in gen.collectors)
        return gen, server, elapsed
    finally:
        gen.close()
        server.sock.close()


def test_collectors_send_a_step_a_period():
    """interval_s 10 at pace 100: a step every 0.1 s, so 0.55 s hold the
    steps due at 0, 0.1 ... 0.5 and no more."""
    gen, server, elapsed = _paced(0.0)
    assert gen.period_s == pytest.approx(0.1)
    assert gen.edge.step == 2 + 6
    assert gen.points_sent() == server.puts == 8 * 4 * 2
    assert 0.55 <= elapsed < 0.7
    assert gen.late_steps()[0] == 0
    # Every block of every collector went out whole, in step.
    assert gen.series_sent(1, 3).tolist() == list(range(8))


def test_a_step_acknowledged_a_period_late_is_counted():
    """A daemon that answers a barrier after 0.25 s: a step takes two
    and a half periods, so each is later than the one before, and no
    step is skipped to catch up."""
    gen, _server, _elapsed = _paced(0.25)
    late, worst = gen.late_steps()
    sent = gen.edge.step - 2
    assert sent in (2, 3)
    # Due at 0, 0.1 ... 0.5; acknowledged at 0.25, 0.5, 0.75 (where the
    # third began in time): all six are late, the last by the least.
    assert late >= 5 and worst >= 0.3


# -- the span a traced run records -----------------------------------------

class _Stopped(Exception):
    pass


def _span(monkeypatch, cycle_s, workers, min_s=0.4, seconds=3.0):
    """``begin_window``'s rule, with workers whose cycles last
    ``cycle_s``: when the trace was stopped, after its start."""
    from benchmarks.lib import daemon as daemon_mod
    monkeypatch.setattr(bench_run, "TRACE_MIN_S", min_s)
    stopped = []

    class Load:
        cycles = [0] * workers
        after_cycle = None

    class FakeDaemon(daemon_mod.Daemon):
        port = 1

        def start(self): pass
        def wait_ready(self, deadline): return 1
        def start_trace(self): self.t0 = time.monotonic()
        def stop_trace(self): stopped.append(time.monotonic() - self.t0)
        def kill(self): pass

    def traffic_of(ctx, daemon, checks):
        load = Load()
        ctx["begin_window"](load if workers else None)
        t0 = time.monotonic()
        ends = [[t0 + c * (k + 1) for k in range(int(seconds / c) + 1)]
                for c in cycle_s[:workers]]
        while time.monotonic() - t0 < seconds and not stopped:
            for w, mine in enumerate(ends):
                if mine and time.monotonic() >= mine[0]:
                    mine.pop(0)
                    load.cycles[w] += 1
                    load.after_cycle()
            time.sleep(0.005)
        ctx["end_window"]()
        raise _Stopped
    monkeypatch.setattr(bench_run, "Daemon", FakeDaemon)
    monkeypatch.setitem(bench_run.KINDS, "queries", traffic_of)
    monkeypatch.setattr(bench_run, "get_store", lambda *a: (
        os.path.join(HERE, "recorded"), {"points": 0}))
    monkeypatch.setattr(bench_run.stats, "get_json", lambda *a: {
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}})
    monkeypatch.setattr(bench_run.stats, "read_stats", lambda port: {
        "tsd.devwindow.points.appended": 0})
    spec = rehearsal_cells.cells()
    args = bench_run.argparse.Namespace(
        workload="cpu4k.dash-1h", seed=1, seconds=seconds, trace=1,
        keep=False)
    with pytest.raises(_Stopped):
        bench_run.run(args, spec, True)
    return stopped[0]


def test_the_span_holds_two_cycles_of_each_worker_and_twenty_seconds(
        monkeypatch):
    """With the 20 s scaled to 0.4: short cycles end at the least span,
    long ones at the slower worker's second cycle, collectors alone at
    the least span, and the window's end stops what is still on."""
    assert (bench_run.TRACE_MIN_S, bench_run.TRACE_CYCLES) == (20.0, 2)
    assert _span(monkeypatch, [0.1, 0.15], 2) == pytest.approx(0.4, abs=0.15)
    assert _span(monkeypatch, [0.3, 0.5], 2) == pytest.approx(1.0, abs=0.15)
    assert _span(monkeypatch, [], 0) == pytest.approx(0.4, abs=0.15)
    assert _span(monkeypatch, [0.3, 0.5], 2, seconds=0.7) == pytest.approx(
        0.7, abs=0.15)
    # No file names a span: one rule for every cell.
    for sub in ("configs", "traffic", os.path.join("tests", "rehearsal")):
        assert all("trace_seconds" not in load(sub, name)
                   for name in os.listdir(os.path.join(BENCH, sub)))


def test_a_twenty_second_span_of_a_52_second_window():
    """Requests of 4 s back to back from t = 100 to 152, the profiler
    on from 100 to 120: five whole requests inside. One more straddles
    the span's end by half; every later one weighs nothing."""
    req = client.Request("double-groupby-1", "/q", ["m"], 0, 10, 1, 1000)
    done = [client.Done(req, 104.0 + 4 * k, 4000.0, True, "", None, 0)
            for k in range(13)]
    done.append(client.Done(req, 122.0, 4000.0, True, "", None, 1))
    ctx = {"kind": "queries", "done": done, "window_s": 52.0,
           "trace": {"busy_s": 11.0, "t_start": 100.0, "t_stop": 120.0},
           "device_kind": "TPU v5 lite"}
    _tr, inside = layers._traced_requests(ctx)
    assert sum(w for _d, w in inside) == pytest.approx(5.5)
    assert layers.evaluate(load("layers", "kernel_ms_per_q.json"),
                           ctx) == pytest.approx(11000.0 / 5.5)
    assert layers.evaluate(load("layers", "kernel_hbm_share.json"),
                           ctx) == pytest.approx(
        100 * 4 * 5.5 * 1000 / (819e9 * 11.0))
    # What reads the client's clock reads the requests answered inside
    # the span (the profile is written beside the rest), what reads
    # /stats the whole window.
    for d in done[5:]:
        d.ms = 9000.0
    assert layers.evaluate(load("layers", "q_p50_ms.json"), ctx) == 4000.0
    del ctx["trace"]
    assert layers.evaluate(load("layers", "q_p50_ms.json"), ctx) == 9000.0


def test_the_reduced_trace_counts_what_ran_inside_the_span():
    """The profiler goes on recording after it is told to stop: an
    operation counts by its part between the span's bounds on the
    profile's own clock."""
    from benchmarks.lib import xplane
    ops = [("%a = f32[] x()", int(0.2e9), int(0.5e9)),   # before the bounds
           ("%b = y", int(0.9e9), int(0.4e9)),           # 0.3 s inside
           ("%c = z", int(5.0e9), int(2.0e9)),
           ("%d = w", int(20.5e9), int(1.0e9)),          # 0.5 s inside
           ("%e = v", int(21.5e9), int(0.4e9))]          # after them
    planes = [("/device:TPU:0", [("XLA Ops", ops)])]
    whole = xplane.reduce_planes(planes)
    assert whole["busy_s"] == pytest.approx(4.3)
    cut = xplane.reduce_planes(planes, (int(1.0e9), int(21.0e9)))
    assert cut["busy_s"] == pytest.approx(0.3 + 2.0 + 0.5)
    assert dict(map(tuple, cut["device_ops"])) == pytest.approx(
        {"b": 0.3, "c": 2.0, "d": 0.5})
    assert cut["idle_gaps"][0] == ["before d", pytest.approx(13.5)]


def test_a_live_window_reads_as_both_kinds():
    """No layer file names the kind: a live window's context holds
    requests and acknowledged points, so a reader of either reads."""
    req = client.Request("double-groupby-1", "/q", ["m"], 0, 10, 1, 1000)
    ctx = {"kind": "live", "window_s": 10.0, "points": 2000,
           "done": [client.Done(req, 104.0, 4000.0, True, "", None, 0)],
           "before": {"tsd.ingest.parse.sum_ms": 5.0},
           "after": {"tsd.ingest.parse.sum_ms": 25.0}}
    assert layers.evaluate(load("layers", "q_p50_ms.json"), ctx) == 4000.0
    assert layers.evaluate(load("layers", "parse_ms_per_kpt.json"),
                           ctx) == 10.0
    assert layers.evaluate(load("layers", "parse_ms_per_kpt.json"),
                           dict(ctx, kind="queries")) is None
    assert layers.evaluate(load("layers", "q_p50_ms.json"),
                           dict(ctx, kind="load")) is None
    assert all("live" not in load("layers", name)["kinds"]
               for name in os.listdir(os.path.join(BENCH, "layers")))
    # A split name with no file of its own is read by its quantity's.
    assert layers.find(BENCH, "parse_ms_per_kpt.live") == layers.find(
        BENCH, "parse_ms_per_kpt")
    assert layers.find(BENCH, "compiles_in_window.load").endswith(
        "compiles_in_window.load.json")
    with pytest.raises(FileNotFoundError):
        layers.find(BENCH, "no_such_metric.live")


# -- a store built as its config says --------------------------------------

def test_a_config_without_store_keeps_the_parents_key(tmp_path):
    with open(os.path.join(HERE, "recorded", "targets-seed7.json")) as f:
        recorded = json.load(f)
    bench_json = load("..", "BENCHMARK.json")
    for conf in bench_json["configs"]:
        cfg = tsbs.load_config(os.path.join(ROOT, conf["file"]))
        assert bench_run.store_key(cfg, 7) == recorded["store_keys"][
            conf["name"]]
    cfg = load("configs", "tsbs-cpu4k.json")
    coded = dict(cfg, store={"sstable_codec": "tsst4"})
    assert bench_run.store_key(coded, 7) != bench_run.store_key(cfg, 7)
    for name, store in (("ok", {"sstable_codec": "tsst4"}),
                        ("bad", {"sstable_codec": "tsst4", "fsync": 1})):
        with open(tmp_path / f"{name}.json", "w") as f:
            json.dump(dict(cfg, store=store), f)
    assert tsbs.load_config(str(tmp_path / "ok.json"))["store"] == {
        "sstable_codec": "tsst4"}
    with pytest.raises(ValueError, match="fsync"):
        tsbs.load_config(str(tmp_path / "bad.json"))


def test_a_store_is_built_in_the_codec_its_config_names(tmp_path):
    """The builder hands ``store`` to the program's Config: the loaded
    sstables of such a store are TSST4, where the default's are not."""
    small = dict(load("tests", "rehearsal", "tsbs-cpu8.json"), hours=1)
    magics = {}
    for name, cfg in (("plain", small), ("coded", dict(
            small, store={"sstable_codec": "tsst4"}))):
        out = tmp_path / name
        with open(tmp_path / f"{name}.json", "w") as f:
            json.dump(cfg, f)
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.lib.store", "build",
             str(tmp_path / f"{name}.json"), "3", str(out)], cwd=ROOT,
            env=bench_run.child_env(cpu=True), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-2000:]
        meta = json.loads(res.stdout.strip().splitlines()[-1])
        assert meta["points"] == 8 * 10 * 360
        tables = [f for f in os.listdir(out) if ".sst.g" in f]
        assert tables
        with open(out / tables[0], "rb") as f:
            magics[name] = f.read(5)
    assert magics["coded"] == b"TSST4" and magics["plain"] != b"TSST4"


# -- the kinds that were there draw what they drew -------------------------

@pytest.mark.parametrize("cell", ["cpu4k.dash-1h", "cpu100.dash-12h",
                                  "cpu4k-13h.hist-12h",
                                  "cpu4k-hbm.dash-12h"])
def test_seed_7_draws_the_parents_targets(cell):
    with open(os.path.join(HERE, "recorded", "targets-seed7.json")) as f:
        recorded = json.load(f)["targets"][cell]
    bench_json = load("..", "BENCHMARK.json")
    entry = next(w for w in bench_json["workloads"] if w["name"] == cell)
    conf = next(c for c in bench_json["configs"]
                if c["name"] == entry["config"])
    cfg = tsbs.load_config(os.path.join(ROOT, conf["file"]))
    qload = client.QueryLoad(cfg, load("traffic", entry["traffic"] + ".json"),
                             7, 0, traced=False)
    assert len(recorded) == qload.workers
    for index, want in enumerate(recorded):
        rng = tsbs.rng(7, 100 + index)
        got = []
        while len(got) < 50:
            got += [r.target for r in qload.cycle(index, rng)]
        assert got[0] == want["first"]
        assert [hashlib.sha256(t.encode()).hexdigest()[:16]
                for t in got[:50]] == want["sha256_16"]


# -- the pending cell, rehearsed; and its controls --------------------------

def test_the_mix_is_dash_1h_anchored():
    dash, live = load("traffic", "dash-1h.json"), load("traffic",
                                                       "live-1h.json")
    assert live["kind"] == "live" and live["workers"] == dash["workers"]
    assert all(t["anchor"] == "edge" for t in live["types"])
    assert [{k: v for k, v in t.items() if k != "anchor"}
            for t in live["types"]] == dash["types"]
    cell = load("pending", CELL + ".json")
    spec = rehearsal_cells.cells()
    listed = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    dash_listed = {m["name"] for m in spec["per_layer"]
                   if "cpu4k.dash-1h" in m["workloads"]}
    assert dash_listed <= listed
    assert listed - dash_listed == {
        "parse_ms_per_kpt.live", "wal_ms_per_kpt.live", "fsyncs_per_kpt.live",
        "checkpoint_busy_share.live", "upload_stalls.live"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in cell["per_layer"]:
        with open(layers.find(BENCH, m["name"])) as f:
            layer = json.load(f)
        # The put side's five are the load cell's files under split
        # names: the entry says what they move in this cell.
        assert m["moves"] == "q_mean_ms" and (
            layer["moves"] == m["moves"] or m["name"].endswith(".live"))
        assert (layer["unit"], layer["source"], layer["layer"]) == (
            m["unit"], m["source"], m["layer"])
    # The root file's own cells are as they were.
    root = load("..", "BENCHMARK.json")
    assert CELL not in json.dumps(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace):
    res, line, lines = bench(CELL, trace, seed=(1 << 31) + 79, seconds=12)
    assert res.returncode == 0, res.stderr[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["device"]) == DEVICE_KEYS
    got = {k: v["value"] for k, v in line["metrics"].items()}
    checks = {ln.split()[1]: float(ln.split()[3]) for ln in lines
              if ln.startswith("check ")}
    assert set(checks) >= {
        "exact_answers_unequal", "f32_max_rel_err", "answers_wrong_shape",
        "answers_ahead_of_edge", "count_minus_acknowledged",
        "sampled_series_unequal", "recount_after_kill_minus_acknowledged",
        "put_error_lines", "collector_late_steps"}
    assert checks["collector_late_steps"] == 0.0
    assert "edge 6 -> 8 (moved at +" in res.stderr     # due at 0 and 10 s
    if not trace:
        assert set(got) == {"q_mean_ms", "queries_per_s", "setup_s"}
        assert all(v > 0 for v in got.values())
        return
    spec = rehearsal_cells.cells()
    listed = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert set(got) == listed - device_metrics()
    assert got["resident_share"] == 100.0
    assert got["parse_ms_per_kpt.live"] > 0
    assert got["wal_ms_per_kpt.live"] > 0


@pytest.mark.parametrize("control,row", [
    ("drop_staged_steps", "exact_answers_unequal"),
    ("late_staged_steps", "f32_max_rel_err"),
    ("wal_unflushed", "recount_after_kill_minus_acknowledged"),
])
def test_control_comes_out_not_correct(control, row):
    res, line, lines = bench(CELL, control=control, seconds=12)
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is False
    assert any(ln.startswith(f"check {row} ") and ln.endswith("FAIL")
               for ln in lines)
