"""chip_smoke.py — the served path, end to end, on the chip.

One command that drives the system the way a deployment does and fails
unless the chip really served:

  [4 collector procs] --telnet put--> [tsd daemon, default backend]
                                         WAL -> memtable -> sstables
                                         device window in HBM
  [this proc] ---------- HTTP ---------> /q /distinct /sketch /stats

then SIGTERMs the daemon, starts it again on the same store (the device
window reloads from WAL + sstables) and repeats the exact-count checks.

Deployment, made from ``--seed`` (source: TSBS ``cpu-only`` use case at
its commonly published scale of 4,000 hosts and a 10 s interval, written
from memory of the TSBS README — see PERF.md "Cells"): 10 metrics x
4,000 hosts x 10 s x 4 h = 57.6M points in 40,000 series, tags host /
region / datacenter, arriving time-major over 4 collector connections
with disjoint hosts. That is 86% of what a default daemon keeps resident
(``device_window_points``), so nothing is evicted and every downsampled
query is served from the device. ``reduced`` in the result lists every
cut of that scale.

One process owns the chip: this parent never imports jax (expected
answers are numpy float64), the collectors import neither jax nor the
package, and nothing but the daemon opens JAX while the daemon lives.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}``
only when every check held on a TPU. With ``JAX_PLATFORMS=cpu`` set by
name and a small ``--hosts/--hours`` the same command is a rehearsal
(last line ``{"rehearsal": true, "platform": "cpu", ...}``); with no
chip and no such request it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback
import urllib.parse
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chip_smoke_out")

# First point: 2013-01-01 00:30:00 UTC. Half past the hour, so that even
# a one-hour load spans two 1 h buckets and `rate` over `1h-avg` has a
# bucket pair to difference; aligned to the 5 m and 10 m buckets.
T0 = 1356998400 + 1800
STEP = 10                       # seconds between points (TSBS interval)
SLICE_STEPS = 30                # a collector sends 5-minute slices
COLLECTORS = 4
FULL_HOSTS, FULL_HOURS = 4000, 4.0
# What the 1200 s contract holds at the rate the served path loads
# (45k points/s measured on the chip host, PR 21): hours are the only
# cut, the rest of the deployment keeps its scale.
DEFAULT_HOURS = 2.0
GAUGES = ("cpu.usage_user", "cpu.usage_system", "cpu.usage_idle",
          "cpu.usage_nice", "cpu.usage_iowait", "cpu.usage_irq",
          "cpu.usage_softirq", "cpu.usage_steal", "cpu.usage_guest")
# TSBS's tenth cpu gauge (usage_guest_nice) gives way to one monotone
# integer counter, so that `rate` runs over a real counter.
COUNTER = "cpu.context_switches"
METRICS = GAUGES + (COUNTER,)
REGIONS = ("us-east-1", "us-west-1", "us-west-2", "eu-west-1",
           "eu-central-1", "ap-southeast-1", "ap-southeast-2",
           "ap-northeast-1", "sa-east-1")
REHEARSAL_MAX_POINTS = 2_000_000
TIME_LIMIT_S = 1150.0           # the contract allows 1200

# Declared contracts the answers are held to.
F32_RTOL = 1e-4                 # f32 sum/avg/rate kernels (PR 18)
TDIGEST_RTOL = 0.02             # merged streaming digests
#                                 (tests/test_livesketch.py)

_START = time.monotonic()
_DEADLINE = _START + TIME_LIMIT_S


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def remaining(cap: float = 1e9) -> float:
    left = _DEADLINE - time.monotonic()
    if left <= 0:
        raise SmokeFailure("time limit reached")
    return min(left, cap)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# The data, from the seed — shared by the collectors (which send it) and
# the parent (which computes what the daemon must answer).
# ---------------------------------------------------------------------------

def host_tags(host: int) -> bytes:
    region = REGIONS[host % len(REGIONS)]
    dc = region + "abc"[(host // len(REGIONS)) % 3]
    return (f" host=host_{host} region={region} datacenter={dc}\n"
            .encode())


def metric_values(seed: int, metric: int, hosts: int,
                  steps: int) -> np.ndarray:
    """[hosts, steps] int32. Gauges: a clipped random walk in hundredths
    of a percent (4237 is sent as ``42.37``). The counter: a running sum
    of non-negative integer increments (stays below 2^24, so its f32
    image on the device is exact)."""
    rng = np.random.default_rng([seed, metric])
    if METRICS[metric] == COUNTER:
        inc = rng.integers(0, 2000, size=(hosts, steps), dtype=np.int32)
        return np.cumsum(inc, axis=1, dtype=np.int32)
    start = rng.integers(1000, 9000, size=(hosts, 1))
    walk = start + np.cumsum(rng.normal(0.0, 60.0, size=(hosts, steps)),
                             axis=1)
    return np.clip(np.rint(walk), 0, 10000).astype(np.int32)


def collector_hosts(index: int, hosts: int) -> range:
    per = -(-hosts // COLLECTORS)
    return range(index * per, min((index + 1) * per, hosts))


# ---------------------------------------------------------------------------
# Collector process: telnet `put` over the socket, no jax, no package.
# ---------------------------------------------------------------------------

def run_collector(spec: str) -> int:
    port, index, hosts, steps, seed = (int(x) for x in spec.split(":"))
    mine = collector_hosts(index, hosts)
    data = [metric_values(seed, m, hosts, steps)[mine.start:mine.stop]
            for m in range(len(METRICS))]
    gauge_text = [b"%d.%02d" % divmod(v, 100) for v in range(10001)]
    ts_text = [b"%d " % (T0 + STEP * k) for k in range(steps)]
    suffix = [host_tags(h) for h in mine]
    sock = socket.create_connection(("127.0.0.1", port))
    replies = bytearray()

    def drain_replies() -> None:
        sock.setblocking(False)
        try:
            while True:
                got = sock.recv(65536)
                if not got:
                    break
                replies.extend(got)
        except BlockingIOError:
            pass
        finally:
            sock.setblocking(True)

    sent = 0
    t0 = time.time()
    for s0 in range(0, steps, SLICE_STEPS):
        ts_slice = ts_text[s0:s0 + SLICE_STEPS]
        for m, name in enumerate(METRICS):
            pre = b"put " + name.encode() + b" "
            rows = data[m][:, s0:s0 + SLICE_STEPS].tolist()
            parts = []
            for suf, row in zip(suffix, rows):
                if name == COUNTER:
                    vals = [t + b"%d" % v for t, v in zip(ts_slice, row)]
                else:
                    vals = [t + gauge_text[v]
                            for t, v in zip(ts_slice, row)]
                parts.append(pre + (suf + pre).join(vals) + suf)
                sent += len(row)
            sock.sendall(b"".join(parts))
            drain_replies()
    # The daemon answers commands in order: when the version banner
    # arrives, every put before it has been applied and acknowledged.
    sock.sendall(b"version\n")
    sock.settimeout(600)
    while b"opentsdb_tpu " not in replies:
        got = sock.recv(65536)
        if not got:
            break
        replies.extend(got)
    sock.close()
    lines = bytes(replies).decode("utf-8", "replace").splitlines()
    errors = [ln for ln in lines if ln.startswith("put:")]
    print(json.dumps({"collector": index, "sent": sent,
                      "wall_s": time.time() - t0,
                      "banner": any(ln.startswith("opentsdb_tpu ")
                                    for ln in lines),
                      "errors": len(errors), "first_errors": errors[:5]}))
    return 0


# ---------------------------------------------------------------------------
# Daemon handling
# ---------------------------------------------------------------------------

def start_daemon(name: str, chips: int, env: dict, rehearsal: bool):
    logpath = os.path.join(OUT, name + ".log")
    cmd = [sys.executable, "-m", "opentsdb_tpu.tools.cli", "tsd",
           "--port", "0", "--bind", "127.0.0.1",
           "--wal", os.path.join(OUT, "store", "wal"),
           "--cachedir", os.path.join(OUT, "qcache"),
           "--auto-metric"]
    # Checkpoints every minute under the real load; a rehearsal's load
    # lasts seconds, so its timer runs every 2 s (checked each second).
    cmd += (["--checkpoint-interval", "2", "--flush-interval", "1"]
            if rehearsal else ["--checkpoint-interval", "60"])
    if chips > 1:
        cmd += ["--mesh", str(chips), "--devwindow-shards", str(chips)]
    logf = open(logpath, "w")
    try:
        proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=logf,
                                stderr=subprocess.STDOUT)
    finally:
        logf.close()
    return proc, logpath


def wait_ready(proc, logpath: str, name: str) -> int:
    """The port from the daemon's complete ready line."""
    while True:
        with open(logpath) as f:
            for ln in f:
                if ln.startswith("Ready to serve on ") \
                        and ln.endswith("\n"):
                    return int(ln.strip().rsplit(":", 1)[1])
        if proc.poll() is not None:
            with open(logpath) as f:
                tail = f.read()[-3000:]
            raise SmokeFailure(f"{name} exited {proc.returncode} during "
                               f"startup:\n{tail}")
        remaining()
        time.sleep(0.5)


def stop_daemon(proc, name: str) -> None:
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=remaining())
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"{name} did not exit on SIGTERM") from None
    check(rc == 0, f"{name} exited {rc} on SIGTERM, wanted 0")


def http_json(port: int, target: str):
    t0 = time.perf_counter()
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{target}",
                                timeout=remaining(900)) as r:
        body = r.read()
    return json.loads(body), (time.perf_counter() - t0) * 1000.0


def stats(port: int) -> dict:
    """/stats lines ("name ts value tags...") -> {"name{tags}": value},
    tags minus the host tag."""
    out = {}
    for ln in http_json(port, "/stats?json")[0]:
        w = ln.split()
        tags = ",".join(t for t in w[3:] if not t.startswith("host="))
        out[w[0] + ("{" + tags + "}" if tags else "")] = float(w[2])
    return out


def checkpoints(port: int) -> int:
    return int(stats(port).get(
        "tsd.checkpoint.phase.count{phase=commit}", 0))


def scan_log(logpath: str, name: str) -> None:
    with open(logpath) as f:
        text = f.read()
    for marker in ("Traceback (most recent call last)",
                   "devwindow upload failed"):
        if marker in text:
            at = text.index(marker)
            raise SmokeFailure(f"{name} log holds {marker!r}:\n"
                               f"{text[max(at - 500, 0):at + 2500]}")


def cache_entries(path: str) -> int:
    return sum(len(files) for _root, _dirs, files in os.walk(path))


# ---------------------------------------------------------------------------
# Expected answers (numpy float64 over the f32 values the daemon stores)
# ---------------------------------------------------------------------------

def buckets(vals: np.ndarray, interval: int, how: str) -> np.ndarray:
    """[hosts, steps] -> [hosts, buckets] statistic over the epoch-
    aligned buckets of ``interval`` seconds that the points fall into
    (the first and last may be partly filled)."""
    hosts, steps = vals.shape
    per = interval // STEP
    lead = (T0 % interval) // STEP
    nb = -(-(lead + steps) // per)
    v = np.pad(vals, ((0, 0), (lead, nb * per - lead - steps)),
               constant_values=np.nan).reshape(hosts, nb, per)
    if how == "avg":
        return np.nanmean(v, axis=2)
    if how == "max":
        return np.nanmax(v, axis=2)
    if how == "count":
        return (~np.isnan(v)).sum(axis=2).astype(np.float64)
    raise ValueError(how)


def bucket_ts(steps: int, interval: int) -> list[int]:
    first = T0 - T0 % interval
    last = T0 + STEP * (steps - 1)
    return list(range(first, last + 1, interval))


def compare(name: str, got: dict, want_ts, want_vals, rtol: float) -> float:
    """Exact timestamp grid; values within rtol (0 = exactly equal).
    Returns the max relative error."""
    want = {str(int(t)): float(v) for t, v in zip(want_ts, want_vals)}
    check(sorted(got) == sorted(want),
          f"{name}: timestamps differ: got {sorted(got)[:4]}.. "
          f"({len(got)}), want {sorted(want)[:4]}.. ({len(want)})")
    worst = 0.0
    for t, w in want.items():
        err = abs(got[t] - w) / max(abs(w), 1e-30)
        check(err <= rtol, f"{name}: at {t} got {got[t]!r}, want {w!r} "
                           f"(rel err {err:.3g} > {rtol})")
        worst = max(worst, err)
    return worst


class Requests:
    """The request set, each sent twice (cold = first call, compiles;
    warm = second), answers checked against numpy."""

    def __init__(self, port: int, hosts: int, steps: int, seed: int):
        self.port, self.hosts, self.steps = port, hosts, steps
        self.end = T0 + STEP * (steps - 1)
        self.user = (metric_values(seed, METRICS.index(GAUGES[0]), hosts,
                                   steps) / 100.0) \
            .astype(np.float32).astype(np.float64)
        self.ctr = metric_values(seed, METRICS.index(COUNTER), hosts,
                                 steps).astype(np.float64)
        self.report: list[dict] = []

    def by_region(self, grid: np.ndarray, interval: int, reduce) -> dict:
        """Per-region answers from a [hosts, buckets] grid: ``reduce``
        folds each region's member rows into one [buckets] row."""
        region = np.arange(self.hosts) % len(REGIONS)
        return {(("region", name),): (bucket_ts(self.steps, interval),
                                      reduce(grid[region == ri]))
                for ri, name in enumerate(REGIONS) if (region == ri).any()}

    def q(self, name: str, m: str, start: int, end: int, want_plan: str,
          want: dict, rtol: float) -> None:
        """``want``: {tags-tuple: (timestamps, values)} per result."""
        target = (f"/q?start={start}&end={end}&m="
                  + urllib.parse.quote(m, safe=":") + "&json&nocache")
        rec = {"request": name, "m": m}
        for leg in ("cold_ms", "warm_ms"):
            body, ms = http_json(self.port, target)
            rec[leg] = round(ms, 2)
            got = {tuple(sorted(r["tags"].items())): r for r in body}
            check(sorted(got) == sorted(want),
                  f"{name}: result groups differ: got {sorted(got)}, "
                  f"want {sorted(want)}")
            worst = 0.0
            for key, (wts, wvals) in want.items():
                r = got[key]
                check(r["rollup"] == want_plan,
                      f"{name}: served by plan {r['rollup']!r}, wanted "
                      f"{want_plan!r}")
                worst = max(worst, compare(f"{name}{dict(key)}", r["dps"],
                                           wts, wvals, rtol))
            rec["plan"] = want_plan
            rec["max_rel_err"] = worst
        self.report.append(rec)
        log(f"{name}: plan={rec['plan']} cold={rec['cold_ms']} ms "
            f"warm={rec['warm_ms']} ms max_rel_err={rec['max_rel_err']:.3g}")

    # -- the requests --------------------------------------------------

    def r1_sum_avg(self):
        want = buckets(self.user, 3600, "avg").sum(axis=0)
        self.q("1:sum:1h-avg", f"sum:1h-avg:{GAUGES[0]}", T0, self.end,
               "resident", {(): (bucket_ts(self.steps, 3600), want)},
               F32_RTOL)

    def r2_rate(self):
        avg = buckets(self.ctr, 3600, "avg")
        rate = (np.diff(avg, axis=1) / 3600.0).sum(axis=0)
        self.q("2:sum:rate:1h-avg", f"sum:rate:1h-avg:{COUNTER}", T0,
               self.end, "resident",
               {(): (bucket_ts(self.steps, 3600)[1:], rate)}, F32_RTOL)

    def r3_p95_groupby(self):
        self.q("3:p95:10m-avg{region=*}",
               f"p95:10m-avg:{GAUGES[0]}{{region=*}}", T0, self.end,
               "resident",
               self.by_region(buckets(self.user, 600, "avg"), 600,
                              lambda rows: np.percentile(rows, 95, axis=0)),
               F32_RTOL)

    def r4_max_and_count(self):
        self.q("4:max:5m-max{region=*}",
               f"max:5m-max:{GAUGES[0]}{{region=*}}", T0, self.end,
               "resident",
               self.by_region(buckets(self.user, 300, "max"), 300,
                              lambda rows: rows.max(axis=0)), 0.0)
        cnt = buckets(self.user, 3600, "count").sum(axis=0)
        check(int(cnt.sum()) == self.hosts * self.steps, "count oracle")
        self.q("4:sum:1h-count", f"sum:1h-count:{GAUGES[0]}", T0,
               self.end, "resident",
               {(): (bucket_ts(self.steps, 3600), cnt)}, 0.0)

    def r5_raw_one_host(self):
        host = self.hosts // 2
        hour = range(self.steps - 3600 // STEP, self.steps)  # the last
        ts = [T0 + STEP * k for k in hour]
        tags = dict(t.split("=") for t in host_tags(host).decode().split())
        self.q("5:sum{host=one},raw",
               f"sum:{GAUGES[0]}{{host=host_{host}}}", ts[0], ts[-1],
               "raw", {tuple(sorted(tags.items())):
                       (ts, self.user[host, hour.start:hour.stop])},
               F32_RTOL)

    def r6_sketches(self):
        rec = {"request": "6:/distinct"}
        for leg in ("cold_ms", "warm_ms"):
            body, ms = http_json(
                self.port, f"/distinct?metric={GAUGES[0]}&tagk=host")
            rec[leg] = round(ms, 2)
            bound = float(body["approx"]["error"])
            check(abs(body["distinct"] - self.hosts) <= bound,
                  f"/distinct {body['distinct']} vs {self.hosts} hosts: "
                  f"outside the declared HLL bound +-{bound:.1f}")
            rec.update(distinct=body["distinct"], declared_bound=bound,
                       max_rel_err=abs(body["distinct"] - self.hosts)
                       / self.hosts)
        self.report.append(rec)
        log(f"/distinct: {rec}")
        rec = {"request": "6:/sketch"}
        exact = np.quantile(self.user.reshape(-1), [0.5, 0.95, 0.99])
        for leg in ("cold_ms", "warm_ms"):
            body, ms = http_json(
                self.port, f"/sketch?m={GAUGES[0]}&q=p50,p95,p99")
            rec[leg] = round(ms, 2)
            check(body["series"] == self.hosts,
                  f"/sketch merged {body['series']} series, wanted "
                  f"{self.hosts}")
            got = [body["quantiles"][k] for k in ("0.5", "0.95", "0.99")]
            errs = [abs(g - e) / abs(e) for g, e in zip(got, exact)]
            check(max(errs) <= TDIGEST_RTOL,
                  f"/sketch {got} vs exact {exact.tolist()}: rel err "
                  f"{max(errs):.3g} > {TDIGEST_RTOL}")
            rec.update(quantiles=got, exact=exact.tolist(),
                       max_rel_err=float(max(errs)))
        self.report.append(rec)
        log(f"/sketch: {rec}")


def device_counters(port: int, want_points: int, leg: str) -> dict:
    st = stats(port)
    got = {k: int(st.get("tsd.devwindow." + k, -1))
           for k in ("points.appended", "points.evicted",
                     "dirty_fallbacks", "upload_stalls",
                     "points.resident")}
    check(got["points.appended"] == want_points,
          f"{leg}: devwindow.points.appended {got['points.appended']} "
          f"!= points stored {want_points}")
    for k in ("points.evicted", "dirty_fallbacks", "upload_stalls"):
        check(got[k] == 0, f"{leg}: devwindow.{k} = {got[k]}, wanted 0")
    return got


def check_device(port: int, rehearsal: bool, chips: int, cache_dir: str):
    hz, _ = http_json(port, "/healthz")
    dev = hz["device"]
    want = "cpu" if rehearsal else "tpu"
    check(dev["platform"] == want,
          f"daemon serves from platform {dev['platform']!r}, wanted "
          f"{want!r}")
    check(dev["count"] >= chips,
          f"daemon sees {dev['count']} devices, --chips {chips}")
    check(hz["compile_cache_dir"] == cache_dir,
          f"daemon's compile cache is {hz['compile_cache_dir']!r}, "
          f"wanted {cache_dir!r}")
    return hz


def check_mesh(hz: dict, chips: int) -> dict:
    res = hz["mesh"]["resident"]
    check(res["shards"] == chips, f"{res['shards']} shards, wanted {chips}")
    ids = res["shard_devices"]
    check(None not in ids and len(set(ids)) == chips,
          f"shards sit on devices {ids}, wanted {chips} distinct")
    check(all(p > 0 for p in res["shard_points"]),
          f"a shard holds no points: {res['shard_points']}")
    return res


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def run(args, rehearsal: bool, result: dict) -> None:
    hosts = args.hosts
    steps = int(round(args.hours * 3600)) // STEP
    points = hosts * steps * len(METRICS)
    result.update({
        "deployment": {"source": "TSBS cpu-only, 4000 hosts x 10 s",
                       "hosts": hosts, "hours": args.hours,
                       "step_s": STEP, "metrics": len(METRICS),
                       "series": hosts * len(METRICS), "points": points,
                       "tags": ["host", "region", "datacenter"],
                       "collectors": COLLECTORS, "arrival": "time-major, "
                       "5-minute slices", "seed": args.seed},
        "reduced": [f"{what} {full:g} -> {got:g}" for what, full, got in
                    (("hosts", FULL_HOSTS, hosts),
                     ("hours", FULL_HOURS, args.hours)) if got != full],
        "chips": args.chips,
    })
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(REPO, ".jax_cache"))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if rehearsal and args.chips > 1:
        # The CPU stand-in for several chips (a CPU-only flag).
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(os.path.join(OUT, "store"))
    procs: list = []
    try:
        # -- leg 1: boot, load, requests -------------------------------
        t_boot = time.monotonic()
        d1, log1 = start_daemon("tsd1", args.chips, env, rehearsal)
        procs.append(d1)
        port = wait_ready(d1, log1, "daemon 1")
        hz = check_device(port, rehearsal, args.chips, cache_dir)
        result["device"] = hz["device"]
        result["compile_cache_dir"] = cache_dir
        result["boot_s"] = round(time.monotonic() - t_boot, 2)
        log(f"daemon 1 up on :{port}, device {hz['device']}")

        t_load = time.monotonic()
        colls = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--collector",
             f"{port}:{i}:{hosts}:{steps}:{args.seed}"],
            stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items()
                 if k != "PYTHONPATH"})
            for i in range(COLLECTORS)]
        procs.extend(colls)
        sent = 0
        for c in colls:
            try:
                out, _ = c.communicate(timeout=remaining())
            except subprocess.TimeoutExpired:
                raise SmokeFailure("collector overran the time limit") \
                    from None
            check(c.returncode == 0, f"collector exited {c.returncode}")
            rep = json.loads(out.strip().splitlines()[-1])
            check(rep["banner"], f"collector {rep['collector']} lost its "
                                 f"connection before the final ack")
            check(rep["errors"] == 0,
                  f"telnet error lines came back: {rep['first_errors']}")
            sent += rep["sent"]
        load_s = time.monotonic() - t_load
        check(sent == points, f"collectors sent {sent}, planned {points}")
        ckpt_in_load = checkpoints(port)
        result["load"] = {"points": sent, "wall_s": round(load_s, 2),
                          "points_per_s": round(sent / load_s, 1),
                          "checkpoints_during_load": ckpt_in_load}
        log(f"loaded {sent:,} points in {load_s:.1f} s "
            f"({sent / load_s:,.0f}/s), {ckpt_in_load} checkpoints")
        # At the real size checkpoints must have completed under load;
        # a rehearsal's load is over in seconds, so it waits for one.
        check(rehearsal or ckpt_in_load >= 1,
              "no checkpoint completed during the load")
        while checkpoints(port) < 1:
            remaining()
            time.sleep(1.0)

        feed = http_json(port, "/api/queries")[0]
        plans0 = feed["plans"]
        result["wire_decoder"] = feed["ingest"]["decoder"]
        reqs = Requests(port, hosts, steps, args.seed)
        reqs.r1_sum_avg()
        reqs.r2_rate()
        reqs.r3_p95_groupby()
        reqs.r4_max_and_count()
        reqs.r5_raw_one_host()
        reqs.r6_sketches()
        result["requests"] = reqs.report
        plans1 = http_json(port, "/api/queries")[0]["plans"]
        served = {k: n - plans0.get(k, 0) for k, n in plans1.items()
                  if n != plans0.get(k, 0)}
        check(served == {"resident": 10, "raw": 2},
              f"/api/queries plans moved by {served}, wanted 10 "
              f"resident + 2 raw")
        result["plans"] = served
        result["counters"] = device_counters(port, points, "leg 1")
        if args.chips > 1:
            result["mesh"] = check_mesh(http_json(port, "/healthz")[0],
                                        args.chips)
        n_cache = cache_entries(cache_dir)
        check(n_cache > 0, f"compile cache {cache_dir} is empty after "
                           f"the first daemon")
        result["cache_entries"] = {"after_leg_1": n_cache}
        stop_daemon(d1, "daemon 1")
        scan_log(log1, "daemon 1")

        # -- leg 2: restart on the same store --------------------------
        t_boot = time.monotonic()
        d2, log2 = start_daemon("tsd2", args.chips, env, rehearsal)
        procs.append(d2)
        port = wait_ready(d2, log2, "daemon 2")
        check_device(port, rehearsal, args.chips, cache_dir)
        result["restart_s"] = round(time.monotonic() - t_boot, 2)
        log(f"daemon 2 up on :{port} after {result['restart_s']} s")
        reqs2 = Requests(port, hosts, steps, args.seed)
        reqs2.r1_sum_avg()
        reqs2.r4_max_and_count()
        result["requests_after_restart"] = reqs2.report
        result["counters_after_restart"] = device_counters(
            port, points, "leg 2")
        if args.chips > 1:
            check_mesh(http_json(port, "/healthz")[0], args.chips)
        result["cache_entries"]["after_leg_2"] = cache_entries(cache_dir)
        stop_daemon(d2, "daemon 2")
        scan_log(log2, "daemon 2")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(os.path.join(OUT, "store"), ignore_errors=True)
        shutil.rmtree(os.path.join(OUT, "qcache"), ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = daemon with --mesh 4 --devwindow-shards 4")
    ap.add_argument("--hosts", type=int, default=FULL_HOSTS)
    ap.add_argument("--hours", type=float, default=DEFAULT_HOURS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--collector", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.collector:
        return run_collector(args.collector)

    if not os.path.isfile(os.path.join(REPO, "opentsdb_tpu", "tools",
                                       "cli.py")):
        print("chip_smoke: the program is not here (no opentsdb_tpu/ "
              "beside this script)", file=sys.stderr)
        return 2
    span = args.hours * 3600
    if args.hosts < COLLECTORS or span < 3600 \
            or span % (STEP * SLICE_STEPS):
        print("chip_smoke: need --hosts >= 4 and --hours >= 1 in whole "
              "5-minute slices (the requests use 1 h buckets)",
              file=sys.stderr)
        return 2
    points = args.hosts * int(span // STEP) * len(METRICS)
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    if rehearsal and points > REHEARSAL_MAX_POINTS:
        print(f"chip_smoke: JAX_PLATFORMS=cpu asks for a CPU rehearsal, "
              f"and a rehearsal is small: {points:,} points is over "
              f"{REHEARSAL_MAX_POINTS:,}. Pass a small --hosts/--hours "
              f"(e.g. --hosts 40 --hours 1); the real size needs the "
              f"chip.", file=sys.stderr)
        return 2

    # A rehearsal's result never carries the chip run's "ok".
    result: dict = ({"rehearsal": True, "platform": "cpu"} if rehearsal
                    else {})
    failure = None
    try:
        run(args, rehearsal, result)
    except SmokeFailure as e:
        failure = str(e)
    except Exception:
        failure = traceback.format_exc()
    result["wall_s"] = round(time.monotonic() - _START, 2)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "result.json"), "w") as f:
        json.dump(dict(result, failure=failure), f, indent=1)
    if failure is not None:
        log(f"FAILED: {failure}")
        return 1
    print(json.dumps(result))
    if rehearsal:
        print(json.dumps({"rehearsal": True, "platform": "cpu",
                          "checks_held": True,
                          "points": result["load"]["points"]}))
    else:
        print(json.dumps({"ok": True, "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
