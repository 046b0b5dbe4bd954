"""chip_smoke.py's contract as far as a CPU can show it: the CPU
rehearsal runs the whole served path at a tiny size and never claims a
chip result; without an explicit, small request it refuses; alone
(without the program) it fails."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_cpu_rehearsal_runs_the_served_path():
    r = _run(["--hosts", "40", "--hours", "1"])
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and last["platform"] == "cpu"
    assert last["points"] == 40 * 360 * 10
    # A rehearsal never prints the chip run's verdict.
    assert '"ok"' not in r.stdout and "pass" not in r.stdout.lower()
    detail = json.loads(lines[0])
    assert detail["rehearsal"] is True
    assert detail["device"]["platform"] == "cpu"
    assert detail["plans"] == {"resident": 10, "raw": 2}
    assert detail["reduced"], "a cut of the scale must be listed"
    assert detail["wire_decoder"] in ("native", "python")
    assert detail["load"]["points"] == last["points"]
    for c in (detail["counters"], detail["counters_after_restart"]):
        assert c["points.appended"] == last["points"]
        assert (c["points.evicted"], c["dirty_fallbacks"],
                c["upload_stalls"]) == (0, 0, 0)
    plans = {q["request"]: q["plan"] for q in detail["requests"]
             if "plan" in q}
    assert plans.pop("5:sum{host=one},raw") == "raw"
    assert set(plans.values()) == {"resident"} and len(plans) == 5
    assert detail["cache_entries"]["after_leg_1"] > 0


def test_refuses_the_real_size_on_the_cpu():
    r = _run([], timeout=60)
    assert r.returncode != 0
    assert r.stdout == ""


def test_fails_without_the_program(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(["--hosts", "40", "--hours", "1"], cwd=str(tmp_path),
             script=str(alone), timeout=60)
    assert r.returncode != 0
    assert r.stdout == ""
