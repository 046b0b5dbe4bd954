"""The load generators: one general generator per kind of traffic file.

``QueryLoad`` reads a mix of ``/q`` request types (``kind: queries``)
and drives it closed-loop, in whole cycles of the mix, from its workers'
threads over persistent HTTP connections, as TSBS's ``run_queries``
does. ``IngestLoad`` reads a
collectors' mix (``kind: load``) and sends telnet ``put`` lines over a
few connections with disjoint hosts, as ``tsbs_load`` does. Both draw
everything from the seed and import neither jax nor the program.

``kind: live`` is the two in one window: the collectors send a step at a
time on a schedule (``IngestLoad.start_paced``) and keep the
acknowledged edge (``Edge``); a request type with ``"anchor": "edge"``
ends at the edge as it stands when the request is drawn.

A request is timed from just before it is written to the socket until
the last byte of the body is read, on ``time.perf_counter``.
"""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time
import urllib.parse

import numpy as np

from benchmarks.lib import tsbs

HTTP_TIMEOUT_S = 120.0


def http_get(port: int, target: str, timeout: float = HTTP_TIMEOUT_S):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", target)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# /q traffic
# ---------------------------------------------------------------------------

class Request:
    """One drawn request: its type, target, and what is needed to check
    the answer (the parsed sub-queries and the window)."""

    __slots__ = ("type", "target", "ms", "start", "end", "groups",
                 "series_steps", "edge")

    def __init__(self, type_, target, ms, start, end, groups,
                 series_steps, edge=None):
        self.type, self.target, self.ms = type_, target, ms
        self.start, self.end = start, end
        self.groups = groups                # results the answer must hold
        self.series_steps = series_steps    # sum of series x steps needed
        self.edge = edge        # the edge step an anchored draw ended at


class Done:
    """One finished request as the client saw it."""

    __slots__ = ("req", "t_wall_end", "ms", "ok", "why", "body", "worker",
                 "results", "resident", "cached", "spans")

    def __init__(self, req, t_wall_end, ms, ok, why, body, worker):
        self.req, self.t_wall_end, self.ms = req, t_wall_end, ms
        self.ok, self.why, self.body, self.worker = ok, why, body, worker
        self.results = self.resident = self.cached = 0
        self.spans: list[dict] = []


def first_traces(body: bytes, metrics: list[str]) -> list[dict]:
    """The span tree of each sub-query of a ``trace=1`` answer: every
    result of a sub-query repeats its tree, so the first one after the
    first result of each metric is taken, without parsing the body."""
    out = []
    dec = json.JSONDecoder()
    for name in metrics:
        at = body.find(b'"metric": "' + name.encode() + b'"')
        at = body.find(b'"trace": ', at) if at >= 0 else -1
        if at < 0:
            continue
        # A tree is small; decode a bounded piece of the body.
        tree, _ = dec.raw_decode(
            body[at + 9:at + 9 + 65536].decode("utf-8", "replace"))
        out.append(tree)
    return out


class Edge:
    """The acknowledged edge of a deployment that is being written to:
    the newest step (counted from the first step after the loaded span;
    0 = nothing sent yet) whose barrier every collector has had
    answered, kept with the wall time it moved. Every point with a
    timestamp up to ``ts(step)`` was acknowledged before ``moved``."""

    def __init__(self, collectors: int, first_ts: int, interval_s: int):
        self.first_ts, self.interval_s = first_ts, interval_s
        self._acked = [0] * collectors
        self._lock = threading.Lock()
        self.step = 0
        self.moved: list[tuple[int, float]] = [(0, time.time())]

    def ts(self, step: int) -> int:
        """Timestamp of edge ``step``; step 0 is the loaded span's last."""
        return self.first_ts + self.interval_s * (step - 1)

    def acknowledged(self, collector: int, steps: int) -> None:
        """Collector ``collector`` has had ``steps`` steps acknowledged."""
        with self._lock:
            self._acked[collector] = steps
            low = min(self._acked)
            if low > self.step:
                self.step = low
                self.moved.append((low, time.time()))

    def read(self) -> tuple[int, int]:
        with self._lock:
            return self.step, self.ts(self.step)

    def moved_at(self, step: int) -> float:
        """Wall time at which the edge reached ``step`` or passed it."""
        with self._lock:
            return next(t for s, t in self.moved if s >= step)


def draw_request(cfg: dict, qtype: dict, rng: np.random.Generator,
                 extra: str = "", metrics: list[str] | None = None,
                 edge: Edge | None = None) -> Request:
    """Draw one request of ``qtype``: ``metrics`` first metrics (TSBS
    takes the first N of its list), ``hosts`` hosts at random (0 = every
    host, ``tag=*``), a window of ``window_s`` starting at a random
    second of the loaded span; a type with ``"anchor": "edge"`` ends at
    the acknowledged edge as it stands now instead (nothing is drawn
    for its window)."""
    step, t0 = int(cfg["interval_s"]), int(cfg["t0"])
    span_end = t0 + step * (tsbs.loaded_steps(cfg) - 1)
    window = int(qtype["window_s"])
    if window > span_end - t0:
        raise ValueError(f"type {qtype['name']}: window {window} s is "
                         f"longer than the loaded span")
    anchor = qtype.get("anchor")
    at = None
    if anchor == "edge":
        if edge is None:
            raise ValueError(f"type {qtype['name']} is anchored to the "
                             f"edge, and this kind of traffic keeps none")
        at, end = edge.read()
        start = end - window
    elif anchor is not None:
        raise ValueError(f"type {qtype['name']}: unknown anchor "
                         f"{anchor!r}")
    else:
        start = t0 + int(rng.integers(0, span_end - t0 - window + 1))
        end = start + window
    nhosts = int(qtype["hosts"])
    if nhosts:
        picks = rng.choice(int(cfg["hosts"]), nhosts, replace=False)
        flt = "{host=" + "|".join(f"host_{int(h)}" for h in picks) + "}"
    else:
        flt = "{host=*}"
    if metrics is None:
        metrics = cfg["metrics"][:int(qtype["metrics"])]
    ms = [f"{qtype['agg']}:{qtype['downsample']}:{name}{flt}"
          for name in metrics]
    target = (f"/q?start={start}&end={end}"
              + "".join("&m=" + urllib.parse.quote(m, safe=":")
                        for m in ms) + "&json" + extra)
    series = (nhosts or int(cfg["hosts"])) * len(ms)
    steps = (end - max(start, t0)) // step + 1
    return Request(qtype["name"], target, ms, start, end, series,
                   series * steps, at)


class QueryLoad:
    def __init__(self, cfg: dict, traffic: dict, seed: int, port: int,
                 traced: bool, edge: Edge | None = None):
        self.cfg, self.traffic, self.seed, self.port = (
            cfg, traffic, seed, port)
        self.edge = edge
        self.types = traffic["types"]
        self.workers = int(traffic["workers"])
        # A traced run's requests carry trace=1 (the span tree comes
        # back inline); that also bypasses the /q disk cache, so the
        # hit share is read in the untraced shape of the request only.
        self.extra = "&trace=1" if traced else ""
        self.done: list[Done] = []
        # Whole cycles each worker has finished, and who is told of one.
        self.cycles = [0] * self.workers
        self.after_cycle = None
        self._lock = threading.Lock()

    def warm(self) -> list[Done]:
        """One request of every type, from a stream of its own. A type's
        sub-queries run one program once per metric, so a warm-up
        request asks only for the metrics no earlier one has touched,
        and for one at least: every shape and every metric's window is
        warm, at a fifth of the device time."""
        rng = tsbs.rng(self.seed, 77)
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=HTTP_TIMEOUT_S)
        out, touched = [], set()
        try:
            for qtype in sorted(self.types, key=lambda t: -int(t["metrics"])):
                names = self.cfg["metrics"][:int(qtype["metrics"])]
                fresh = [n for n in names if n not in touched] or names[:1]
                touched.update(fresh)
                req = draw_request(self.cfg, qtype, rng, self.extra, fresh,
                                   self.edge)
                out.append(self._one(conn, req, -1, keep=False))
        finally:
            conn.close()
        return out

    def _one(self, conn, req: Request, worker: int, keep: bool = True
             ) -> Done:
        t0 = time.perf_counter()
        try:
            conn.request("GET", req.target)
            resp = conn.getresponse()
            body = resp.read()
            ms = (time.perf_counter() - t0) * 1000.0
            status = resp.status
        except (OSError, http.client.HTTPException) as e:
            conn.close()
            return Done(req, time.time(), (time.perf_counter() - t0) * 1e3,
                        False, f"{type(e).__name__}: {e}", None, worker)
        groups = body.count(b'"metric":')
        if status != 200:
            ok, why = False, f"HTTP {status}: {body[:200]!r}"
        elif groups != req.groups:
            ok, why = False, f"{groups} results, wanted {req.groups}"
        else:
            ok, why = True, ""
        done = Done(req, time.time(), ms, ok, why,
                    body if (keep or not ok) else None, worker)
        done.results = groups
        done.resident = body.count(b'"rollup": "resident"')
        done.cached = body.count(b'"cached": true')
        if self.extra and ok:
            done.spans = first_traces(
                body, self.cfg["metrics"][:len(req.ms)])
        return done

    def cycle(self, index: int, rng: np.random.Generator):
        """One whole cycle of the mix as worker ``index`` draws it, in
        the file's order (worker i starts i/workers of the way in):
        every seed and every run issues the same sizes in the same
        order, and only hosts and windows are drawn. A request is drawn
        when the one before it has been answered."""
        n = len(self.types)
        for k in range(n):
            qtype = self.types[(index * n // self.workers + k) % n]
            yield draw_request(self.cfg, qtype, rng, self.extra,
                               edge=self.edge)

    def _worker(self, index: int, t_end: float) -> None:
        rng = tsbs.rng(self.seed, 100 + index)
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=HTTP_TIMEOUT_S)
        mine = []
        # Whole cycles: a cycle begun before the window's end is
        # finished, so the statistics never depend on where the clock
        # cut a cycle.
        try:
            while time.perf_counter() < t_end:
                for req in self.cycle(index, rng):
                    mine.append(self._one(conn, req, index))
                self.cycles[index] += 1
                if self.after_cycle is not None:
                    self.after_cycle()
        finally:
            conn.close()
            with self._lock:
                self.done.extend(mine)

    def run(self, seconds: float) -> float:
        """Closed loop for ``seconds``, then to the end of each worker's
        cycle; every request is counted. Returns the window's length."""
        t0 = time.perf_counter()
        threads = [threading.Thread(target=self._worker,
                                    args=(i, t0 + seconds), daemon=True)
                   for i in range(self.workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# telnet put traffic
# ---------------------------------------------------------------------------

class Collector:
    """One connection's share of the hosts. ``steps_sent[mi][block]``
    counts, per metric, how many steps after the loaded span went out
    for each of its host blocks (a block goes out whole or not at all)."""

    def __init__(self, load: "IngestLoad", index: int):
        self.load, self.index = load, index
        n = int(load.cfg["hosts"])
        per = -(-n // load.workers)
        self.hosts = range(index * per, min((index + 1) * per, n))
        self.sock: socket.socket | None = None
        self.replies = bytearray()
        self.points = 0
        # steps_sent[mi][block] -> steps of that block sent so far
        nblocks = -(-len(self.hosts) // load.hosts_per_send)
        self.steps_sent = [[0] * nblocks
                           for _ in load.cfg["metrics"]]
        self.error_lines = 0
        self.lost = ""
        self.tail_sent = False

    def connect(self) -> None:
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # A small send buffer, so that what is "sent" is close to what
        # the daemon has read, and the barrier has little to wait for.
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 18)
        self.sock.connect(("127.0.0.1", self.load.port))

    def _drain(self) -> None:
        self.sock.setblocking(False)
        try:
            while True:
                got = self.sock.recv(65536)
                if not got:
                    break
                self.replies.extend(got)
        except BlockingIOError:
            pass
        finally:
            self.sock.setblocking(True)

    def send_steps(self, s0: int, s1: int, deadline=None,
                   tail: bool = False) -> None:
        """Steps [s0, s1) relative to the first step after the loaded
        span, metric by metric, block of hosts by block; stops between
        blocks once ``deadline()`` has passed."""
        ld = self.load
        for mi, name in enumerate(ld.cfg["metrics"]):
            pre = b"put " + name.encode() + b" "
            data = ld.values[mi]
            for bi, h0 in enumerate(range(0, len(self.hosts),
                                          ld.hosts_per_send)):
                if deadline is not None \
                        and time.perf_counter() >= deadline():
                    return
                hs = self.hosts[h0:h0 + ld.hosts_per_send]
                rows = data[s0:s1, hs.start:hs.stop].T.tolist()
                ts = ld.ts_text[s0:s1]
                parts = []
                for h, row in zip(hs, rows):
                    suf = ld.suffix[h]
                    vals = [t + ld.gauge[v] for t, v in zip(ts, row)]
                    parts.append(pre + (suf + pre).join(vals) + suf)
                self.sock.sendall(b"".join(parts))
                n = len(hs) * (s1 - s0)
                self.points += n
                if not tail:
                    self.steps_sent[mi][bi] += s1 - s0
                self._drain()
        self.tail_sent = self.tail_sent or tail

    def barrier(self, timeout: float = 300.0) -> bool:
        """The daemon answers commands in order: when the version banner
        arrives, every put before it has been applied and acknowledged."""
        mark = len(self.replies)
        self.sock.sendall(b"version\n")
        self.sock.settimeout(timeout)
        try:
            while b"opentsdb_tpu " not in self.replies[max(mark - 16, 0):]:
                got = self.sock.recv(65536)
                if not got:
                    self.lost = "connection closed before the barrier"
                    return False
                self.replies.extend(got)
        except OSError as e:
            self.lost = f"{type(e).__name__}: {e}"
            return False
        self.error_lines = sum(
            1 for ln in bytes(self.replies).split(b"\n")
            if ln.startswith(b"put:"))
        return True

    def close(self) -> None:
        if self.sock is not None:
            self.sock.close()
            self.sock = None


class IngestLoad:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 max_seconds: float):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        # A live mix's ``workers`` are its dashboards.
        self.workers = int(traffic["collectors"] if "collectors" in traffic
                           else traffic["workers"])
        self.slice_steps = int(traffic.get("slice_steps", 1))
        self.pace = float(traffic["pace"]) if "pace" in traffic else None
        self.warm_steps = int(traffic["warm_steps"])
        self.hosts_per_send = int(traffic["hosts_per_send"])
        self.tail_steps = int(traffic["tail_steps"])
        self.port = 0
        step, t0 = int(cfg["interval_s"]), int(cfg["t0"])
        self.loaded = tsbs.loaded_steps(cfg)
        series = int(cfg["hosts"]) * len(cfg["metrics"])
        # Enough steps for the longest window: on the schedule of a
        # paced mix, or at a rate the daemon cannot reach.
        if self.pace is not None:
            self.period_s = step / self.pace
            room = int(max_seconds / self.period_s) + 1
        else:
            room = int(max_seconds * float(traffic["max_points_per_s"])
                       / series)
        self.extra = (self.warm_steps + self.tail_steps
                      + (room // self.slice_steps + 2) * self.slice_steps)
        self.values = [
            tsbs.metric_values(cfg, seed, mi, self.loaded + self.extra)
            [self.loaded:] for mi in range(len(cfg["metrics"]))]
        self.first_ts = t0 + step * self.loaded
        self.ts_text = [b"%d " % (self.first_ts + step * k)
                        for k in range(self.extra)]
        self.gauge = tsbs.gauge_text()
        tags = tsbs.host_tag_table(cfg, seed)
        self.suffix = [(" " + " ".join(f"{k}={v}" for k, v in t.items())
                        + "\n").encode() for t in tags]
        self.collectors = [Collector(self, i) for i in range(self.workers)]
        self.edge = Edge(self.workers, self.first_ts, step)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self.t0_wall = self.t_stop_wall = self.t_end_wall = 0.0

    def _start(self, fn) -> list[threading.Thread]:
        threads = [threading.Thread(target=fn, args=(c,), daemon=True)
                   for c in self.collectors]
        for t in threads:
            t.start()
        return threads

    def _all(self, fn) -> None:
        for t in self._start(fn):
            t.join()

    def warm(self, port: int) -> bool:
        self.port = port
        for c in self.collectors:
            c.connect()

        def go(c: Collector) -> None:
            c.send_steps(0, self.warm_steps, None)
            if c.barrier():
                self.edge.acknowledged(c.index, self.warm_steps)
        self._all(go)
        return not any(c.lost for c in self.collectors)

    def run(self, deadline) -> float:
        """Closed loop: every collector sends slice after slice as fast
        as the daemon reads, until ``deadline()`` (a ``perf_counter``
        time, which may still move while the window runs) has passed,
        then its barrier. Returns window start -> last barrier reply,
        in seconds."""
        t0 = time.perf_counter()

        def go(c: Collector) -> None:
            s = self.warm_steps
            last = self.extra - self.tail_steps
            while time.perf_counter() < deadline() \
                    and s + self.slice_steps <= last:
                c.send_steps(s, s + self.slice_steps, deadline)
                s += self.slice_steps
            if time.perf_counter() < deadline():
                c.lost = "ran out of steps before the window's end"
            c.barrier()
        self._all(go)
        return time.perf_counter() - t0

    def start_paced(self) -> None:
        """Open loop by schedule: step k after the warm-up is due at
        now + k x ``period_s`` (``interval_s`` / ``pace``). A collector
        sends its hosts' step for every metric once it is due and the
        step before is acknowledged, then its barrier, and tells the
        edge. Returns at once; ``finish_paced`` ends it."""
        self.t0_wall = time.time()

        def go(c: Collector) -> None:
            last = self.extra - self.tail_steps
            k = 0
            while not self._stop.wait(
                    max(self.t0_wall + k * self.period_s - time.time(), 0)):
                s = self.warm_steps + k
                if s >= last:
                    c.lost = "ran out of steps before the window's end"
                    return
                c.send_steps(s, s + 1)
                if not c.barrier():
                    return
                self.edge.acknowledged(c.index, s + 1)
                k += 1
        self._threads = self._start(go)

    def finish_paced(self) -> float:
        """The window has ended: no collector begins another step; each
        finishes the one it is in and takes its barrier. Returns window
        start -> last barrier reply, in seconds."""
        self.t_stop_wall = time.time()
        self._stop.set()
        for t in self._threads:
            t.join()
        self.t_end_wall = time.time()
        return self.t_end_wall - self.t0_wall

    def late_steps(self) -> tuple[int, float]:
        """Steps of the paced window acknowledged (by every collector)
        more than one period after they were due, and the worst
        lateness in seconds. A step that was due before the window's
        end and never sent is late by what it had waited when the last
        barrier came back."""
        late, worst = 0, 0.0
        due_steps = -int(-(self.t_stop_wall - self.t0_wall) // self.period_s)
        for k in range(due_steps):
            due = self.t0_wall + k * self.period_s
            at = self.warm_steps + k + 1
            by = (self.edge.moved_at(at) if self.edge.step >= at
                  else self.t_end_wall)
            worst = max(worst, by - due)
            late += by - due > self.period_s
        return late, worst

    def tail(self) -> bool:
        """One more step from the first collector, acknowledged, for the
        kill that follows at once: the steps kept back at the end, so
        they never collide with a slice."""
        c = self.collectors[0]
        s = self.extra - self.tail_steps
        c.send_steps(s, self.extra, None, tail=True)
        return c.barrier()

    def points_sent(self) -> int:
        return sum(c.points for c in self.collectors)

    def series_sent(self, metric: int, host: int) -> np.ndarray:
        """Indices (relative steps) of the points of one series that
        went out, in time order."""
        for c in self.collectors:
            if host in c.hosts:
                bi = (host - c.hosts.start) // self.hosts_per_send
                break
        else:
            raise ValueError(f"host {host} belongs to no collector")
        # A block's steps go out in order from 0 (the warm steps, then
        # whole slices); the tail is the steps kept back at the end.
        idx = list(range(c.steps_sent[metric][bi]))
        if c.tail_sent:
            idx += range(self.extra - self.tail_steps, self.extra)
        return np.asarray(idx, dtype=np.int64)

    def close(self) -> None:
        for c in self.collectors:
            c.close()
