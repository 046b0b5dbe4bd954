"""Every cell end to end on the CPU at 40 (or 8) hosts: the real daemon
over sockets, the whole of a run except the look for a chip.

Slow (a minute or two): each case starts a daemon. Run with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.tests import rehearsal_cells

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = rehearsal_cells.PATH
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "rehearsal"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module", autouse=True)
def rehearsal_json():
    rehearsal_cells.write()


def bench(workload, trace=0, seed=5, seconds=3, control="", env_cpu=True,
          bench_json=REHEARSAL):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    if env_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_RUN"] = "ignored"
    cmd = [sys.executable, "-m"] + (
        ["benchmarks.tests.run_control", control] if control
        else ["benchmarks.run"]) + [
        "--workload", workload, "--seed", str(seed), "--seconds",
        str(seconds), "--trace", str(trace), "--benchmark-json", bench_json]
    res = subprocess.run(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True, timeout=600)
    lines = res.stdout.strip().splitlines()
    return res, (json.loads(lines[-1]) if lines and lines[-1].startswith("{")
                 else None), lines


def device_metrics():
    return {m["name"] for m in rehearsal_cells.cells()["per_layer"]
            if m["source"] == "device_trace"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["cpu4k.dash-1h", "cpu4k.load",
                                      "cpu100.dash-12h"])
def test_cell_rehearsal(workload, trace):
    res, line, lines = bench(workload, trace, seed=(1 << 31) + 77)
    assert res.returncode == 0, res.stderr[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    # No device figure off the chip: no busy time, no trace metric.
    assert set(line["device"]) == DEVICE_KEYS
    assert not set(line["metrics"]) & device_metrics()
    spec = rehearsal_cells.cells()
    if trace:
        listed = {m["name"] for m in spec["per_layer"]
                  if workload in m["workloads"]}
        assert set(line["metrics"]) == listed - device_metrics()
    else:
        listed = {m["name"] for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])}
        assert set(line["metrics"]) == listed
        assert all(m["value"] > 0 for m in line["metrics"].values())
    # Each number compared is printed beside its limit.
    assert sum(ln.startswith("check ") for ln in lines) >= 4


@pytest.mark.parametrize("workload,control", [
    ("cpu4k.dash-1h", "wire_bf16"),          # the lower precision
    ("cpu100.dash-12h", "wire_bf16"),
    ("cpu4k.load", "wal_unflushed"),         # the ack before the flush
    ("cpu4k.dash-1h", "answer_off_4e-3"),    # the timed path, broken
    ("cpu4k.load", "drop_last_point"),
])
def test_control_comes_out_not_correct(workload, control):
    res, line, lines = bench(workload, control=control)
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is False
    assert any(ln.startswith("check ") and ln.endswith("FAIL")
               for ln in lines)


def test_deployment_size_is_refused_on_the_cpu():
    res, line, _ = bench("cpu4k.dash-1h",
                         bench_json=os.path.join(ROOT, "BENCHMARK.json"))
    assert res.returncode != 0 and line is None
    assert "rehearsal" in res.stderr


def test_silent_cpu_is_refused():
    """No chip and no request for the CPU by name: the daemon refuses to
    boot, the run fails and prints no result."""
    try:
        import jax
        if jax.default_backend() == "tpu":
            pytest.skip("a chip is attached here")
    except ImportError:
        pytest.skip("no jax")
    res, line, _ = bench("cpu4k.dash-1h", env_cpu=False)
    assert res.returncode != 0 and line is None


def test_without_the_program_nothing_runs(tmp_path):
    """A directory with BENCHMARK.json and benchmarks/ alone."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "out",
                                                  "__pycache__"))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "cpu4k.dash-1h", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=120)
    assert res.returncode != 0 and res.stdout.strip() == ""
