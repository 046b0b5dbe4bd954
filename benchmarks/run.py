"""One run of one cell: ``python -m benchmarks.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>``, from the root of a checkout.

The parent (this process) never imports jax or the program: the one
process that owns the chip is the daemon under test. A run

1. gets the cell's store from ``benchmarks/.cache/stores`` or builds it
   there once per (config, seed) with a CPU-pinned child
   (``benchmarks/lib/store.py``);
2. starts the real daemon, with the argv the config file gives, on a
   copy of the store (a run never dirties the cache);
3. checks the device and that the device window took in every stored
   point, and warms one request of every type of the cell's traffic
   (a load or live cell: sends and has acknowledged the first steps);
4. measures for ``--seconds``; a ``--trace 1`` run records the profiler
   from the window's start until every worker has finished two whole
   cycles of its mix, 20 s at the least, and not the whole window
   (``TRACE_MIN_S``, ``TRACE_CYCLES``);
5. checks the answers, outside the window, against the numpy reference
   of ``benchmarks/lib/tsbs.py``;
6. prints each number compared beside its limit, then the result line.

``setup_s`` is 1-3. There is no fallback: where the daemon does not
serve from a TPU the run fails and prints no result. A CPU rehearsal
is asked for by name (``JAX_PLATFORMS=cpu``) with a config marked
``rehearsal`` (a tiny one, see ``benchmarks/tests``); its line says
``"rehearsal": true`` and carries no device metric.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np

from benchmarks.lib import client, layers, stats, tsbs
from benchmarks.lib.daemon import Daemon, DaemonFailure

T_START = time.monotonic()
BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")
OUT = os.path.join(BENCH, "out")
# Stores kept for later runs of their (config, seed): a set of runs has
# 6 seeds, and its second set finds them. The least recently used go
# first. A store is ~450 MB on disk.
MAX_STORES = 6
READY_TIMEOUT_S = 900.0
# A traced run records the profiler from the window's start until every
# worker has finished TRACE_CYCLES whole cycles of its mix (a fair share
# of every type, in the file's order), TRACE_MIN_S at the least, and
# never past the window's end. A whole window's profile took
# cpu100.dash-12h 98-114 s to write (PERF.md section 5).
TRACE_MIN_S = 20.0
TRACE_CYCLES = 2


class RunFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def child_env(cpu: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return env


# ---------------------------------------------------------------------------
# The store cache
# ---------------------------------------------------------------------------

def store_key(cfg: dict, seed: int) -> str:
    shape = [cfg[k] for k in ("hosts", "interval_s", "hours", "t0",
                              "metrics", "tags")]
    if "store" in cfg:      # how the store is built, where a config says
        shape.append(cfg["store"])
    shape = json.dumps(shape, sort_keys=True)
    return (f"{cfg['name']}-{seed}-"
            + hashlib.sha256(shape.encode()).hexdigest()[:8])


def get_store(cfg: dict, cfg_path: str, seed: int) -> tuple[str, dict]:
    root = os.path.join(CACHE, "stores")
    path = os.path.join(root, store_key(cfg, seed))
    meta_path = os.path.join(path, "STORE.json")
    if not os.path.isfile(meta_path):
        os.makedirs(root, exist_ok=True)
        tmp = path + ".building"
        shutil.rmtree(tmp, ignore_errors=True)
        shutil.rmtree(path, ignore_errors=True)
        log(f"building store {os.path.basename(path)}")
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.lib.store", "build",
             cfg_path, str(seed), tmp],
            cwd=REPO, env=child_env(cpu=True), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        if res.returncode != 0:
            raise RunFailure(f"store build exited {res.returncode}:\n"
                             f"{res.stderr[-3000:]}")
        os.rename(tmp, path)
    os.utime(meta_path)
    kept = sorted((d for d in os.listdir(root)
                   if os.path.isfile(os.path.join(root, d, "STORE.json"))),
                  key=lambda d: os.path.getmtime(
                      os.path.join(root, d, "STORE.json")))
    for old in kept[:-MAX_STORES]:
        shutil.rmtree(os.path.join(root, old), ignore_errors=True)
    with open(meta_path) as f:
        return path, json.load(f)


def cache_files(path: str | None) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _r, _d, files in os.walk(path))


# ---------------------------------------------------------------------------
# Checks: each number compared, beside its limit
# ---------------------------------------------------------------------------

class Checks:
    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    def ok(self) -> bool:
        return all(abs(v) <= lim for _n, v, lim in self.rows)

    def show(self) -> None:
        for name, v, lim in self.rows:
            print(f"check {name} = {v!r} limit {lim!r} "
                  f"{'ok' if abs(v) <= lim else 'FAIL'}", flush=True)


def check_answers(cfg: dict, traffic: dict, seed: int,
                  done: list, checks: Checks, rtol: float,
                  edge: client.Edge | None = None) -> None:
    """A sample of the window's answers, drawn from the seed, with the
    longest in it, against the reference. With an ``edge`` (a store
    written to in the window) the reference is taken over the loaded
    steps plus those sent up to the edge's last stand: every point up
    to a request's ``end`` was acknowledged before the request was
    written, so its answer is determined; and the sample holds, of each
    type, the first request written after each move of the edge, the
    soonest after its move first: the answers that a point which
    becomes visible late (a stage or ``/q`` cache not yet invalidated,
    a staged batch not yet drained) would be missing from."""
    kept = [d for d in done if d.ok and d.body is not None]
    if not kept:
        checks.add("answers_compared_missing", 1, 0)
        return
    rng = tsbs.rng(seed, 55)
    kept.sort(key=lambda d: (d.worker, d.t_wall_end))
    longest = max(range(len(kept)), key=lambda i: len(kept[i].body))
    order = [longest] + [int(i) for i in rng.permutation(len(kept))
                         if i != longest]
    steps = tsbs.loaded_steps(cfg)
    pick, lags = [longest], []
    if edge is not None:
        steps += edge.step
        first: dict[tuple[int, str], tuple[float, int]] = {}
        for i, d in enumerate(kept):
            if d.req.edge is None:
                continue
            lag = (d.t_wall_end - d.ms / 1000.0
                   - edge.moved_at(d.req.edge))
            key = (d.req.edge, d.req.type)
            if key not in first or lag < first[key][0]:
                first[key] = (lag, i)
        lags = sorted(first.values())
        pick += [i for _lag, i in lags if i != longest]
    # Every type of the mix next, then by the draw.
    seen = {kept[i].req.type for i in pick}
    for i in order:
        if kept[i].req.type not in seen:
            seen.add(kept[i].req.type)
            pick.append(i)
    pick += [i for i in order if i not in pick]
    pick = pick[:int(traffic["check_max"])]
    near = [lag for lag, i in lags if i in pick]
    if near:
        log(f"of the requests compared {len(near)} are the first of their "
            f"type after a move of the edge, {sum(x < 1.0 for x in near)} "
            f"written within 1 s of it, the soonest "
            f"{min(near) * 1000.0:.0f} ms after")
    if edge is not None:
        # An anchored request ends at an edge that had been
        # acknowledged when it was written.
        checks.add("answers_ahead_of_edge", sum(
            d.req.end != edge.ts(d.req.edge)
            or edge.moved_at(d.req.edge) > d.t_wall_end - d.ms / 1000.0
            for d in (kept[i] for i in pick) if d.req.edge is not None), 0)
    tags = tsbs.host_tag_table(cfg, seed)
    values: dict[int, np.ndarray] = {}
    worst_f32, exact_bad, shape_bad, compared, tokens = 0.0, 0, 0, 0, 0
    for i in pick:
        d = kept[i]
        body = json.loads(d.body)
        by_metric: dict[str, list] = {}
        for r in body:
            by_metric.setdefault(r["metric"], []).append(r)
        for m_text in d.req.ms:
            m = tsbs.parse_m(m_text)
            mi = cfg["metrics"].index(m["metric"])
            if mi not in values:
                values[mi] = tsbs.metric_values(cfg, seed, mi, steps)
            want = tsbs.reference(cfg, tags, values[mi], m, d.req.start,
                                  d.req.end)
            got = by_metric.get(m["metric"], [])
            exact = (m["down"][1] in tsbs.EXACT_AGGS
                     and m["agg"] in tsbs.EXACT_AGGS)
            if len(got) != len(want):
                shape_bad += 1
                continue
            for r in got:
                key = tuple(sorted((k, r["tags"].get(k)) for k in m["tags"]
                                   if m["tags"][k] == "*"
                                   or "|" in m["tags"][k]))
                if key not in want:
                    shape_bad += 1
                    continue
                err = tsbs.compare(r["dps"], *want[key],
                                   0.0 if exact else rtol)
                compared += 1
                tokens += len(r["dps"])
                if exact:
                    exact_bad += err != 0.0
                else:
                    worst_f32 = max(worst_f32, err)
    log(f"compared {compared} results ({tokens} values) of {len(pick)} "
        f"requests, types {sorted(seen)}")
    checks.add("answers_wrong_shape", shape_bad, 0)
    checks.add("exact_answers_unequal", exact_bad, 0)
    checks.add("f32_max_rel_err", worst_f32, rtol)


# ---------------------------------------------------------------------------
# The kinds of traffic
# ---------------------------------------------------------------------------

def percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def warm_queries(load: client.QueryLoad) -> None:
    for d in load.warm():
        if not d.ok:
            raise RunFailure(f"warm-up request {d.req.type} failed: "
                             f"{d.why}")
        log(f"warm {d.req.type}: {d.ms:.1f} ms")


def query_metrics(ctx: dict, load: client.QueryLoad) -> dict:
    """The window's requests into ``ctx``, the log and the two
    end-to-end metrics: over every request, and all of the window."""
    ctx["done"] = load.done
    ms = [d.ms for d in load.done]
    ctx["attempted"] = len(load.done)
    ctx["failed"] = sum(not d.ok for d in load.done)
    for d in [d for d in load.done if not d.ok][:5]:
        log(f"failed {d.req.type}: {d.why}")
    for qtype in ctx["traffic"]["types"]:
        mine = [d.ms for d in load.done if d.req.type == qtype["name"]]
        if mine:
            log(f"{qtype['name']}: {len(mine)} requests, median "
                f"{percentile(mine, 50):.0f} ms, max {max(mine):.0f} ms")
    log(f"window {ctx['window_s']:.1f} s, {len(ms)} requests, "
        f"{sum(len(d.req.ms) for d in load.done)} sub-queries")
    return {"q_mean_ms": float(np.mean(np.asarray(ms, dtype=np.float64))),
            "queries_per_s": len(ms) / ctx["window_s"]}


def run_queries(ctx: dict, daemon: Daemon, checks: Checks) -> dict:
    cfg, traffic, args = ctx["cfg"], ctx["traffic"], ctx["args"]
    load = client.QueryLoad(cfg, traffic, args.seed, daemon.port,
                            traced=bool(args.trace))
    warm_queries(load)
    ctx["begin_window"](load)
    ctx["window_s"] = load.run(args.seconds)
    ctx["end_window"]()
    ctx["after_kill"] = lambda: check_answers(
        cfg, traffic, args.seed, load.done, checks,
        float(cfg["guarantees"]["f32_rtol"]))
    return query_metrics(ctx, load)


class CycleClock:
    """Ends a load window on a whole number of the daemon's background
    cycles. The window starts wherever the cycle happens to be; this
    watches one counter of ``/stats`` (``cycle_stat``: it goes up once
    a cycle, for this daemon when a checkpoint commits), takes the mean
    length L of the cycles seen so far, and sets the window's end to
    start + k x L, where k x L is the whole number of cycles nearest
    to the seconds asked for, and k >= ``min_cycles``. So a window
    holds as much of the cycle's slow part as of its fast part, at
    whatever phase it began, and a cycle a little longer or shorter
    does not add or drop a whole one.
    A mix that names no ``cycle_stat`` gets a window of the seconds
    asked for."""

    POLL_S = 0.5

    def __init__(self, port: int, traffic: dict, seconds: float):
        self.port, self.seconds = port, seconds
        self.stat = traffic.get("cycle_stat")
        self.min_cycles = int(traffic.get("min_cycles", 1))
        self.cap_s = seconds * float(traffic.get("max_window_factor", 1))
        self.marks: list[float] = []        # when each cycle was seen to end
        self.cycles = 0
        self.capped = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.t0 = self._end = 0.0

    def start(self) -> None:
        self.t0 = time.perf_counter()
        if not self.stat:
            self._end = self.t0 + self.seconds
            return
        self._end = self.t0 + self.cap_s
        self.capped = True
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def deadline(self) -> float:
        return self._end

    def _read(self) -> float | None:
        try:
            return stats.read_stats(self.port).get(self.stat, 0.0)
        except (OSError, RuntimeError):
            return None

    def _watch(self) -> None:
        seen = self._read()
        while not self._stop.wait(self.POLL_S):
            now = self._read()
            if now is None:
                continue
            if seen is None or now <= seen:
                seen = now
                continue
            seen = now
            self.marks.append(time.perf_counter())
            if len(self.marks) < 2:
                continue
            length = ((self.marks[-1] - self.marks[0])
                      / (len(self.marks) - 1))
            k = max(self.min_cycles, round(self.seconds / length))
            if len(self.marks) >= k:
                if self.t0 + k * length <= self._end:
                    self.cycles = int(k)
                    self._end = self.t0 + k * length
                    self.capped = False
                return

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()


def count_back(ctx: dict, daemon: Daemon, checks: Checks,
               gen: client.IngestLoad) -> int:
    """Every acknowledged point counted back: by the live daemon, then
    a seeded sample of the new series value by value, then one more
    acknowledged step, the SIGKILL, and a recount from the files alone.
    Ends the daemon (its device figures are read first). Returns the
    points and put lines that missed."""
    cfg, traffic, args = ctx["cfg"], ctx["traffic"], ctx["args"]
    step, t0 = int(cfg["interval_s"]), int(cfg["t0"])
    last_ts = gen.first_ts + step * (gen.extra - 1)
    target = (f"/q?start={t0}&end={last_ts}"
              + "".join(f"&m=sum:1h-count:{name}"
                        for name in cfg["metrics"]) + "&json&nocache")
    counted = sum(sum(r["dps"].values())
                  for r in stats.get_json(daemon.port, target, 600.0))
    want = ctx["store_points"] + gen.points_sent()
    checks.add("count_minus_acknowledged", counted - want, 0)
    rng = tsbs.rng(args.seed, 66)
    bad = 0
    for _ in range(int(traffic["check_series"])):
        mi = int(rng.integers(len(cfg["metrics"])))
        h = int(rng.integers(int(cfg["hosts"])))
        idx = gen.series_sent(mi, h)
        if idx.size == 0:
            continue
        res = stats.get_json(daemon.port, (
            f"/q?start={gen.first_ts}&end={last_ts}&m=sum:"
            f"{cfg['metrics'][mi]}%7Bhost=host_{h}%7D&json&nocache"),
            600.0)
        want_v = tsbs.stored(gen.values[mi][idx, h])
        ok = (len(res) == 1 and tsbs.compare(
            res[0]["dps"], gen.first_ts + step * idx, want_v, 0.0)
            == 0.0)
        bad += not ok
    checks.add("sampled_series_unequal", bad, 0)
    ctx["read_device"]()
    if not gen.tail():
        raise RunFailure("the tail's barrier did not come back")
    daemon.kill()
    errors = sum(c.error_lines for c in gen.collectors)
    checks.add("put_error_lines", errors, 0)
    new_points = gen.points_sent()
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.lib.store", "count",
         os.path.join(ctx["work"], "store"), str(gen.first_ts),
         str(last_ts)] + list(cfg["metrics"]),
        cwd=REPO, env=child_env(cpu=True), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    if res.returncode != 0:
        raise RunFailure(f"recount exited {res.returncode}:\n"
                         f"{res.stderr[-3000:]}")
    recount = json.loads(res.stdout.strip().splitlines()[-1])["points"]
    checks.add("recount_after_kill_minus_acknowledged",
               recount - new_points, 0)
    return int(abs(counted - want) + abs(recount - new_points) + errors)


def collectors_sound(gen: client.IngestLoad) -> None:
    lost = [c.lost for c in gen.collectors if c.lost]
    if lost:
        raise RunFailure(f"a collector stopped: {lost[0]}")


def run_load(ctx: dict, daemon: Daemon, checks: Checks) -> dict:
    traffic, args = ctx["traffic"], ctx["args"]
    gen: client.IngestLoad = ctx["ingest"]
    try:
        gen.warm(daemon.port)
        collectors_sound(gen)
        warm_points = gen.points_sent()
        clock = CycleClock(daemon.port, traffic, args.seconds)
        ctx["begin_window"]()
        clock.start()
        try:
            elapsed = gen.run(clock.deadline)
        finally:
            clock.stop()
        ctx["window_s"] = elapsed
        ctx["end_window"]()
        collectors_sound(gen)
        sent = gen.points_sent() - warm_points
        ctx["points"] = sent
        log(f"sent {sent:,} points in {elapsed:.2f} s; cycles ended at "
            + ", ".join(f"+{m - clock.t0:.1f}" for m in clock.marks)
            + f" s; window of {clock.cycles} cycles")
        if clock.capped and not ctx["rehearsal"]:
            raise RunFailure(
                f"the window reached {clock.cap_s:.0f} s before "
                f"{clock.min_cycles} cycles of {clock.stat} were seen")
        ctx["attempted"] = sent
        ctx["failed"] = count_back(ctx, daemon, checks, gen)
    finally:
        gen.close()
    return {"ingest_points_per_s": sent / elapsed}


def run_live(ctx: dict, daemon: Daemon, checks: Checks) -> dict:
    """A deployment that is written to while it is read: the paced
    collectors of ``IngestLoad`` beside the closed-loop workers of
    ``QueryLoad``, joined by the acknowledged edge. The window and the
    end-to-end metrics are ``run_queries``'s, the count-back
    ``run_load``'s; the answers are checked over loaded + sent steps."""
    cfg, traffic, args = ctx["cfg"], ctx["traffic"], ctx["args"]
    gen: client.IngestLoad = ctx["ingest"]
    try:
        gen.warm(daemon.port)
        collectors_sound(gen)
        warm_points = gen.points_sent()
        load = client.QueryLoad(cfg, traffic, args.seed, daemon.port,
                                traced=bool(args.trace), edge=gen.edge)
        warm_queries(load)
        ctx["begin_window"](load)
        gen.start_paced()
        try:
            ctx["window_s"] = load.run(args.seconds)
        finally:
            elapsed = gen.finish_paced()
        ctx["end_window"]()
        collectors_sound(gen)
        metrics = query_metrics(ctx, load)
        ctx["points"] = gen.points_sent() - warm_points
        late, worst = gen.late_steps()
        log(f"collectors: {ctx['points']:,} points acknowledged in "
            f"{elapsed:.2f} s, a step every {gen.period_s:g} s, edge "
            f"{gen.warm_steps} -> {gen.edge.step} (moved at "
            + ", ".join(f"+{t - gen.t0_wall:.1f}" for s, t in gen.edge.moved
                        if s > gen.warm_steps)
            + f" s); {late} steps late, the worst acknowledged "
            f"{worst:.2f} s after it was due")
        # A collector that falls behind is a deployment that cannot
        # take the rate its mix states: not ``correct``, at any pace.
        checks.add("collector_late_steps", late, 0)
        ctx["after_kill"] = lambda: check_answers(
            cfg, traffic, args.seed, load.done, checks,
            float(cfg["guarantees"]["f32_rtol"]), gen.edge)
        ctx["failed"] += count_back(ctx, daemon, checks, gen)
    finally:
        gen.close()
    return metrics


KINDS = {"queries": run_queries, "load": run_load, "live": run_live}
# The kinds whose collectors' data is made while the daemon boots.
WRITING = ("load", "live")


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def reduce_trace(ctx: dict, daemon: Daemon) -> dict | None:
    marks = ctx.get("trace_marks")
    if marks is None:
        return None
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.lib.xplane",
         os.path.join(daemon.sig_dir, "trace"), repr(marks["lead_s"]),
         repr(marks["lead_s"] + marks["t_stop"] - marks["t_start"])],
        cwd=REPO, env=child_env(cpu=True), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    if res.returncode != 0:
        raise RunFailure(f"trace reduction exited {res.returncode}:\n"
                         f"{res.stderr[-3000:]}")
    out = json.loads(res.stdout.strip().splitlines()[-1])
    out.update(t_start=marks["t_start"], t_stop=marks["t_stop"],
               window_s=marks["t_stop"] - marks["t_start"])
    return out


def run(args, bench: dict, rehearsal: bool) -> dict:
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise RunFailure(f"no workload {args.workload!r} in "
                         f"{args.benchmark_json}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg_path = os.path.join(REPO, conf["file"])
    try:
        cfg = tsbs.load_config(cfg_path)
    except ValueError as e:
        raise RunFailure(str(e)) from e
    if rehearsal and not cfg.get("rehearsal"):
        raise RunFailure(
            "JAX_PLATFORMS=cpu asks for a CPU rehearsal, and a rehearsal "
            "is small: this cell's config is a deployment's. Use a copy "
            "of BENCHMARK.json whose configs are those of "
            "benchmarks/tests/rehearsal (benchmarks/README.md); the real "
            "size needs the chip.")
    with open(tsbs.find_file(BENCH, "traffic", cell["traffic"])) as f:
        traffic = json.load(f)
    if traffic["kind"] not in KINDS:
        raise RunFailure(f"traffic kind {traffic['kind']!r} has no "
                         f"generator (known: {sorted(KINDS)})")

    store, meta = get_store(cfg, cfg_path, args.seed)
    work = os.path.join(OUT, args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    shutil.copytree(store, os.path.join(work, "store"))
    traced = bool(args.trace)
    daemon = Daemon(REPO, cfg, work, "tsd")
    checks = Checks()
    ctx: dict = {"cfg": cfg, "traffic": traffic, "args": args,
                 "kind": traffic["kind"], "work": work,
                 "rehearsal": rehearsal,
                 "store_points": int(meta["points"])}
    try:
        daemon.start()
        if traffic["kind"] in WRITING:
            ctx["ingest"] = client.IngestLoad(
                cfg, traffic, args.seed, args.seconds
                * float(traffic.get("max_window_factor", 1)))
        port = daemon.wait_ready(time.monotonic() + READY_TIMEOUT_S)
        hz = stats.get_json(port, "/healthz")
        dev = hz["device"]
        want = "cpu" if rehearsal else "tpu"
        if dev["platform"] != want:
            raise RunFailure(f"daemon serves from platform "
                             f"{dev['platform']!r}, wanted {want!r}")
        if dev["count"] < int(cell["chips"]) and not rehearsal:
            raise RunFailure(f"daemon sees {dev['count']} chips, the cell "
                             f"asks for {cell['chips']}")
        log(f"daemon ready on :{port}, device {dev}")
        checks.add("devwindow_appended_minus_stored",
                   stats.read_stats(port).get(
                       "tsd.devwindow.points.appended", -1)
                   - meta["points"], 0)
        cache_dir = hz.get("compile_cache_dir")

        def begin_window(load: client.QueryLoad | None = None):
            ctx["before"] = stats.read_stats(port) if traced else None
            ctx["cache_before"] = cache_files(cache_dir)
            ctx["setup_s"] = time.monotonic() - T_START
            log(f"window starts; setup_s = {ctx['setup_s']:.2f}")
            if not traced:
                return
            daemon.start_trace()
            t0 = time.monotonic()

            def span_over():
                """Ends the traced span once it has lasted TRACE_MIN_S
                and every worker has finished TRACE_CYCLES cycles: asked
                by a timer at the one and by each worker at the end of
                a cycle (a window of collectors alone has no worker)."""
                if time.monotonic() - t0 >= TRACE_MIN_S and all(
                        n >= TRACE_CYCLES for n in
                        (load.cycles if load is not None else ())):
                    daemon.stop_trace()
            if load is not None:
                load.after_cycle = span_over
            ctx["trace_timer"] = threading.Timer(TRACE_MIN_S, span_over)
            ctx["trace_timer"].daemon = True
            ctx["trace_timer"].start()

        def end_window():
            ctx["compiles"] = cache_files(cache_dir) - ctx["cache_before"]
            if traced:
                ctx["trace_timer"].cancel()
                daemon.stop_trace()
                ctx["after"] = stats.read_stats(port)
                if args.keep:
                    with open(os.path.join(work, "stats.json"), "w") as f:
                        json.dump({"before": ctx["before"],
                                   "after": ctx["after"]}, f)

        def read_device():
            """What only the live daemon can say, once the window is
            over: the traced span's bounds and the device's memory."""
            if traced:
                marks = ctx["trace_marks"] = daemon.trace_result(120.0)
                log(f"traced {marks['t_stop'] - marks['t_start']:.1f} s from "
                    f"{marks['lead_s']:.3f} s into the profile; written "
                    f"in {marks['written_s']:.1f} s")
            ctx["memory"] = daemon.memory()

        ctx["begin_window"], ctx["end_window"] = begin_window, end_window
        ctx["read_device"] = read_device
        metrics = KINDS[traffic["kind"]](ctx, daemon, checks)
        if "memory" not in ctx:
            read_device()
        daemon.kill()
        if "after_kill" in ctx:
            ctx["after_kill"]()
        problem = daemon.scan_log()
        if problem:
            raise RunFailure(f"the daemon's log holds:\n{problem}")
        trace = reduce_trace(ctx, daemon) if traced else None
    except (DaemonFailure, OSError, RuntimeError) as e:
        raise RunFailure(str(e)) from e
    finally:
        daemon.kill()
        if not args.keep:
            shutil.rmtree(os.path.join(work, "store"), ignore_errors=True)
            shutil.rmtree(os.path.join(work, "qcache"), ignore_errors=True)
            if traced:
                shutil.rmtree(os.path.join(daemon.sig_dir, "trace"),
                              ignore_errors=True)

    metrics["setup_s"] = ctx["setup_s"]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    peaks = [d["peak_bytes_in_use"] for d in ctx["memory"]["devices"]
             if d["peak_bytes_in_use"] is not None]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": max(peaks) if peaks else None}
    line: dict = {"correct": False, "attempted": ctx["attempted"],
                  "failed": ctx["failed"]}
    if traced:
        ctx["trace"] = trace
        ctx["device_kind"] = dev["kind"]
        out = {}
        for m in bench["per_layer"]:
            if "workloads" in m and args.workload not in m["workloads"]:
                continue
            with open(layers.find(BENCH, m["name"])) as f:
                layer = json.load(f)
            if rehearsal and layer["source"] == "device_trace":
                continue
            v = layers.evaluate(layer, ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": units[m["name"]]}
        line["metrics"] = out
        if trace and trace["busy_s"] > 0 and not rehearsal:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
    else:
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in metrics.items()}
    line["device"] = device
    if rehearsal:
        line["rehearsal"] = True
    checks.show()
    line["correct"] = checks.ok() and ctx["failed"] == 0
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-json",
                    default=os.path.join(REPO, "BENCHMARK.json"),
                    help="another cell list (the tests' rehearsal cells)")
    ap.add_argument("--keep", action="store_true",
                    help="leave the run's store copy and trace behind, "
                    "and with --trace 1 the window's /stats readings")
    args = ap.parse_args(argv)
    if not os.path.isfile(os.path.join(REPO, "opentsdb_tpu", "tools",
                                       "cli.py")):
        print("benchmarks.run: the program is not here (no opentsdb_tpu/ "
              "beside benchmarks/)", file=sys.stderr)
        return 2
    if args.seed < 0:
        print("benchmarks.run: --seed must not be negative",
              file=sys.stderr)
        return 2
    with open(args.benchmark_json) as f:
        bench = json.load(f)
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    try:
        line = run(args, bench, rehearsal)
    except RunFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
