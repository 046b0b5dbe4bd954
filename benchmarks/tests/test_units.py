"""The yardstick's own arithmetic, on shapes worked out by hand."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import client, layers, roofline, tsbs, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = {"name": "hand", "hosts": 3, "interval_s": 10, "hours": 1,
       "t0": 1000, "metrics": ["cpu.a", "cpu.b"], "tags": ["host", "team"],
       "guarantees": {"f32_rtol": 1e-4}}
TAGS = [{"host": "host_0", "team": "SF"}, {"host": "host_1", "team": "SF"},
        {"host": "host_2", "team": "NYC"}]
# 7 steps at t = 1000..1060; buckets of 30 s start at 990, 1020, 1050.
VALUES = np.array([[100, 200, 300], [110, 210, 310], [150, 250, 350],
                   [120, 220, 320], [90, 190, 290], [130, 230, 330],
                   [170, 270, 370]], np.int32)


def ref(m, start=1000, end=1060):
    return tsbs.reference(CFG, TAGS, VALUES, tsbs.parse_m(m), start, end)


def test_reference_buckets_by_hand():
    out = ref("max:30s-max:cpu.a{host=host_1}")
    ts, vals = out[()]
    assert ts.tolist() == [990, 1020, 1050]
    # f32 images of 2.10, 2.50, 2.70
    assert vals.tolist() == [float(np.float32(x)) for x in (2.1, 2.5, 2.7)]
    ts, vals = ref("avg:30s-avg:cpu.a{host=*}")[(("host", "host_2"),)]
    want = [np.float32([3.0, 3.1]).astype(float).mean(),
            np.float32([3.5, 3.2, 2.9]).astype(float).mean(),
            np.float32([3.3, 3.7]).astype(float).mean()]
    assert vals.tolist() == pytest.approx(want, rel=1e-12)


def test_reference_window_filter_and_groups():
    # start/end cut points, not buckets: 1015..1045 keeps t=1020,1030,1040.
    ts, vals = ref("sum:30s-count:cpu.a", 1015, 1045)[()]
    assert ts.tolist() == [1020] and vals.tolist() == [9.0]
    out = ref("sum:30s-sum:cpu.a{team=*}")
    assert sorted(out) == [(("team", "NYC"),), (("team", "SF"),)]
    sf = out[(("team", "SF"),)][1]
    assert sf[0] == pytest.approx(float(np.float32(1.0)) + float(
        np.float32(1.1)) + float(np.float32(2.0)) + float(np.float32(2.1)))
    two = ref("max:30s-max:cpu.a{host=host_0|host_2}")
    assert sorted(two) == [(("host", "host_0"),), (("host", "host_2"),)]
    raw = ref("sum:cpu.a{host=host_0}", 1010, 1030)[()]
    assert raw[0].tolist() == [1010, 1020, 1030]


def test_compare_limits():
    ts, vals = [10, 20], [1.0, 2.0]
    assert tsbs.compare({"10": 1.0, "20": 2.0}, ts, vals, 0.0) == 0.0
    assert tsbs.compare({"10": 1.0, "20": 2.0000002}, ts, vals, 0.0) \
        == float("inf")
    assert tsbs.compare({"10": 1.0, "20": 2.0002}, ts, vals, 1e-3) \
        == pytest.approx(1e-4)
    assert tsbs.compare({"10": 1.0}, ts, vals, 1e-3) == float("inf")
    assert tsbs.compare({"10": 1.0, "30": 2.0}, ts, vals, 1e-3) \
        == float("inf")


def test_walk_is_prefix_stable_and_clamped():
    cfg = dict(CFG, hosts=5)
    a = tsbs.metric_values(cfg, 7, 0, 50)
    b = tsbs.metric_values(cfg, 7, 0, 2000)
    assert (a == b[:50]).all()
    assert b.min() >= 0 and b.max() <= 10000
    assert (tsbs.metric_values(cfg, 8, 0, 50) != a).any()



def test_big_seed_is_taken():
    cfg = dict(CFG, hosts=5)
    big = (1 << 31) + 12345
    assert tsbs.metric_values(cfg, big, 0, 4).shape == (4, 5)
    assert len(tsbs.host_tag_table(dict(cfg, tags=["host"]), big)) == 5


def _answer(req, scale=1.0):
    """The body a correct daemon would send for ``req``."""
    out = []
    for m_text in req.ms:
        m = tsbs.parse_m(m_text)
        vals = VALUES if m["metric"] == "cpu.a" else VALUES + 7
        for key, (ts, v) in tsbs.reference(CFG, TAGS, vals, m, req.start,
                                           req.end).items():
            out.append({"metric": m["metric"], "tags": dict(key),
                        "dps": {str(int(t)): float(x) * scale
                                for t, x in zip(ts, v)}})
    return json.dumps(out).encode()


def _checked(monkeypatch, scale):
    monkeypatch.setattr(
        tsbs, "metric_values",
        lambda cfg, seed, mi, steps: VALUES if mi == 0 else VALUES + 7)
    monkeypatch.setattr(tsbs, "host_tag_table", lambda cfg, seed: TAGS)
    done = []
    for i, m in enumerate(("avg:30s-avg:cpu.a{host=*}",
                           "max:30s-max:cpu.b{host=host_0|host_1}")):
        req = client.Request(f"t{i}", "/q", [m], 1000, 1060, 0, 0)
        d = client.Done(req, 0.0, 1.0, True, "", _answer(req, scale), 0)
        done.append(d)
    checks = bench_run.Checks()
    bench_run.check_answers(CFG, {"check_max": 8}, 1, done, checks, 1e-4)
    return checks


def test_sound_answers_pass(monkeypatch):
    assert _checked(monkeypatch, 1.0).ok()


def test_answer_perturbed_at_4e_3_fails(monkeypatch):
    """bfloat16 keeps 8 bits of mantissa: an answer rounded to it is off
    by up to 4e-3, and the stated 1e-4 must catch that."""
    checks = _checked(monkeypatch, 1.004)
    assert not checks.ok()
    rows = {n: (v, lim) for n, v, lim in checks.rows}
    assert rows["f32_max_rel_err"][0] == pytest.approx(4e-3, rel=1e-3)
    assert rows["exact_answers_unequal"][0] > 0


def test_roofline_by_hand():
    # 8 hosts x 360 steps x 5 metrics = 14,400 points = 57,600 bytes;
    # 1 ms busy at 819 GB/s moves 819 MB: 0.00703%.
    assert roofline.needed_bytes(8 * 360 * 5) == 57600
    assert roofline.hbm_share_pct(8 * 360 * 5, 1e-3, "TPU v5 lite") \
        == pytest.approx(100 * 57600 / 819e6)
    assert roofline.hbm_share_pct(1, 0.0, "TPU v5 lite") is None
    with pytest.raises(KeyError):
        roofline.hbm_share_pct(1, 1.0, "TPU v9 imaginary")


def test_xplane_union_by_hand():
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 0, 100), ("fusion.2", 50, 100),
                         ("copy.3", 400, 100)]),
            ("XLA Modules", [("jit_f", 0, 1000)])]),
        ("/host:CPU", [("python", [("work", 0, 5000)])]),
    ]
    out = xplane.reduce_planes(planes)
    assert out["device_planes"] == 1
    assert out["busy_s"] == pytest.approx(250e-9)   # [0,150] + [400,500]
    assert out["span_s"] == pytest.approx(500e-9)
    assert out["device_ops"][0][0] in ("fusion.1", "fusion.2", "copy.3")
    assert out["idle_gaps"] == [["before copy.3", pytest.approx(250e-9)]]


def test_xplane_recorded_trace():
    """A trace recorded on the v5e (tests/record_trace.py made it)."""
    path = os.path.join(HERE, "recorded", "tiny.xplane.pb")
    out = xplane.reduce_planes(xplane.read_planes(path))
    with open(os.path.join(HERE, "recorded", "tiny.expected.json")) as f:
        want = json.load(f)
    assert out["device_planes"] == want["device_planes"] == 1
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["span_s"]
    assert [n for n, _ in out["device_ops"]] == [
        n for n, _ in want["device_ops"]]


def test_layer_readers():
    tree = {"name": "query", "ms": 10.0, "spans": [
        {"name": "planner.pick", "ms": 7.0,
         "spans": [{"name": "scan", "ms": 2.0}]}]}
    req = client.Request("single-groupby-1-1-1", "/q", ["m"], 0, 10, 1, 100)
    d = client.Done(req, 5.0, 12.0, True, "", None, 0)
    d.spans, d.results, d.resident, d.cached = [tree, tree], 2, 2, 0
    ctx = {"kind": "queries", "done": [d], "compiles": 0,
           "trace": {"busy_s": 1e-3, "t_start": 0.0, "t_stop": 9.0},
           "device_kind": "TPU v5 lite"}

    def val(name):
        with open(os.path.join(HERE, "..", "layers", name + ".json")) as f:
            return layers.evaluate(json.load(f), ctx)
    assert val("plan_ms") == 14.0
    assert val("frontend_ms") == pytest.approx(12.0 - 20.0)
    assert val("resident_share") == 100.0
    assert val("q_ms.single-groupby") == 12.0
    assert val("q_ms.double-groupby") is None
    assert val("q_p95_ms") == 12.0
    assert val("kernel_ms_per_q") == pytest.approx(1.0)
    assert val("kernel_hbm_share") == pytest.approx(100 * 400 / 819e6)
    assert val("parse_ms_per_kpt") is None     # a load metric, not here


def test_trace_metrics_weigh_a_request_by_its_overlap():
    """A request half inside the traced span counts as half a request
    and brings half of the bytes it needs."""
    req = client.Request("double-groupby-1", "/q", ["m"], 0, 10, 1, 100)
    inside = client.Done(req, 5.0, 2000.0, True, "", None, 0)
    half = client.Done(req, 10.0, 2000.0, True, "", None, 1)    # 8 s..10 s
    outside = client.Done(req, 12.0, 2000.0, True, "", None, 1)
    ctx = {"kind": "queries", "done": [inside, half, outside],
           "trace": {"busy_s": 3.0, "t_start": 0.0, "t_stop": 9.0},
           "device_kind": "TPU v5 lite"}

    def val(name):
        with open(os.path.join(HERE, "..", "layers", name + ".json")) as f:
            return layers.evaluate(json.load(f), ctx)
    assert val("kernel_ms_per_q") == pytest.approx(3000.0 / 1.5)
    assert val("kernel_hbm_share") == pytest.approx(
        100 * 4 * 150 / (819e9 * 3.0))


def test_cycle_clock_ends_on_whole_cycles(monkeypatch):
    """A counter that goes up every 0.3 s, a window asked to last 0.7 s
    and at least 3 cycles: it ends 0.9 s after its start, whatever the
    phase it began at."""
    from benchmarks import run
    t_first = time.perf_counter() + 0.17
    monkeypatch.setattr(run.stats, "read_stats", lambda port: {
        "c": float(int((time.perf_counter() - t_first) // 0.3) + 10)})
    monkeypatch.setattr(run.CycleClock, "POLL_S", 0.01)
    clock = run.CycleClock(0, {"cycle_stat": "c", "min_cycles": 3,
                               "max_window_factor": 5}, 0.7)
    clock.start()
    while time.perf_counter() < clock.deadline():
        time.sleep(0.01)
    clock.stop()
    assert clock.cycles == 3 and not clock.capped
    assert clock.deadline() - clock.t0 == pytest.approx(0.9, abs=0.05)
    # No counter named: the seconds asked for.
    plain = run.CycleClock(0, {}, 0.7)
    plain.start()
    assert plain.deadline() - plain.t0 == pytest.approx(0.7)
    assert not plain.capped


def test_stats_ratio_reads_deltas():
    ctx = {"kind": "load", "points": 2000, "window_s": 10.0,
           "before": {"tsd.ingest.parse.sum_ms": 5.0,
                      "tsd.checkpoint.phase.sum_ms{phase=spill}": 1000.0},
           "after": {"tsd.ingest.parse.sum_ms": 25.0,
                     "tsd.checkpoint.phase.sum_ms{phase=spill}": 3000.0,
                     "tsd.checkpoint.phase.sum_ms{phase=commit}": 500.0}}

    def val(name):
        with open(os.path.join(HERE, "..", "layers", name + ".json")) as f:
            return layers.evaluate(json.load(f), ctx)
    assert val("parse_ms_per_kpt") == 10.0
    assert val("checkpoint_busy_share") == pytest.approx(25.0)


def test_benchmark_json_finds_its_files():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for conf in bench["configs"]:
        cfg = tsbs.load_config(os.path.join(root, conf["file"]))
        assert cfg["name"] == conf["name"]
        assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    for cell in bench["workloads"]:
        tsbs.find_file(os.path.join(root, "benchmarks"), "traffic",
                       cell["traffic"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        with open(tsbs.find_file(os.path.join(root, "benchmarks"),
                                 "layers", m["name"])) as f:
            layer = json.load(f)
        assert layer["reader"] in layers.READERS
        assert (layer["unit"], layer["source"], layer["layer"],
                layer["moves"]) == (m["unit"], m["source"], m["layer"],
                                    m["moves"])
        assert m["moves"] in e2e
