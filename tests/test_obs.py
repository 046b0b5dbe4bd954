"""Observability layer tests: metrics registry, Prometheus exposition
guard, trace spans (incl. the deterministic faultpoint-delay proof),
slow-query log + trace ring, self-monitoring ingest, CLI stats."""

import asyncio
import json
import logging
import re
import time

import numpy as np
import pytest

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.fault import faultpoints
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import (METRICS, MetricsRegistry,
                                       read_rss_bytes)
from opentsdb_tpu.obs.ring import TraceRing, make_record
from opentsdb_tpu.server.tsd import TSDServer
from opentsdb_tpu.stats.collector import StatsCollector
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.storage.sharded import ShardedKVStore
from opentsdb_tpu.utils.config import Config

BASE = 1356998400


# ---------------------------------------------------------------------------
# Prometheus text exposition validator (the tier-1 scraper guard)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})?\s+(\S+)$")


def validate_exposition(text: str) -> int:
    """Assert ``text`` is valid Prometheus text exposition by the rules
    new instrumentation most easily breaks: every sample belongs to a
    family whose ``# TYPE`` line PRECEDES it, families are contiguous
    (one TYPE block each, never re-opened), and no (name, labels)
    sample repeats. Returns the sample count."""
    declared: dict[str, str] = {}
    seen_samples = set()
    current = None
    n = 0
    if not text.strip():
        return 0
    for line in text.rstrip("\n").split("\n"):
        assert line == line.strip(), f"stray whitespace: {line!r}"
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, f"malformed TYPE line: {line!r}"
            _, _, name, ftype = parts
            assert ftype in ("counter", "gauge", "summary", "histogram",
                             "untyped"), f"bad type {ftype!r}"
            assert name not in declared, \
                f"family {name} re-declared (non-contiguous)"
            declared[name] = ftype
            current = name
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        float(value)  # must parse
        assert current is not None, f"sample before any TYPE: {line!r}"
        ftype = declared[current]
        ok_names = {current}
        if ftype == "summary":
            ok_names |= {current + "_count", current + "_sum"}
        assert name in ok_names, \
            f"sample {name} under TYPE block {current} ({ftype})"
        key = (name, labels)
        assert key not in seen_samples, f"duplicate sample {key}"
        seen_samples.add(key)
        n += 1
    return n


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_timer_roundtrip(self):
        r = MetricsRegistry()
        r.counter("c").inc()
        r.counter("c").inc(2)
        r.gauge("g", lambda: 7)
        with r.timer("t").time():
            pass
        r.timer("t").observe(5.0)
        c = StatsCollector("tsd", host_tag=False)
        r.collect(c)
        lines = {ln.split()[0]: ln for ln in c.lines}
        assert lines["tsd.c"].split()[2] == "3"
        assert lines["tsd.g"].split()[2] == "7"
        assert lines["tsd.t.count"].split()[2] == "2"
        assert "tsd.t" in lines  # percentile lines present
        assert any("percentile=99" in ln for ln in c.lines)

    def test_same_key_same_instrument(self):
        r = MetricsRegistry()
        assert r.counter("x") is r.counter("x")
        assert r.counter("x", {"a": "1"}) is not r.counter("x")
        assert r.timer("t", {"s": "0"}) is r.timer("t", {"s": "0"})

    def test_kind_conflict_rejected(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(ValueError):
            r.timer("x")

    def test_failing_gauge_skipped(self):
        r = MetricsRegistry()
        r.gauge("bad", lambda: 1 / 0)
        c = StatsCollector("tsd", host_tag=False)
        r.collect(c)
        assert c.lines == []
        validate_exposition(r.prometheus_text())

    def test_prometheus_text_valid_and_typed(self):
        r = MetricsRegistry()
        r.counter("wal.appends").inc(5)
        r.gauge("mem", lambda: 3.5)
        r.timer("ckpt.phase", {"phase": "freeze"}).observe(10.0)
        r.timer("ckpt.phase", {"phase": "commit"}).observe(20.0)
        text = r.prometheus_text()
        n = validate_exposition(text)
        assert n == 1 + 1 + 2 * 5  # counter + gauge + 2x(3q + count + sum)
        assert "# TYPE tsd_wal_appends counter" in text
        assert "# TYPE tsd_ckpt_phase_ms summary" in text
        assert 'phase="freeze",quantile="0.5"' in text

    def test_prometheus_extra_lines_merge_and_dedup(self):
        r = MetricsRegistry()
        r.counter("dup").inc(9)
        now = int(time.time())
        text = r.prometheus_text(extra_lines=[
            f"tsd.dup {now} 1 host=x",          # registry wins
            f"tsd.classic {now} 2 host=x a=b",
            f"tsd.classic {now} 3 host=x a=b",  # duplicate sample drops
            f"tsd.classic {now} 4 host=x a=c",
            "malformed line",
        ])
        validate_exposition(text)
        assert "tsd_dup 9" in text
        assert text.count('tsd_classic{') == 2
        assert 'a="b"' in text and 'a="c"' in text

    def test_rss_readable(self):
        assert read_rss_bytes() > 1 << 20  # this process is > 1 MiB

    def test_submillisecond_timer_percentiles_survive_collect(self):
        """Regression: int-ms truncation flattened sub-ms timers
        (wal.fsync, chunk decode) — and every self-monitored tsd.*
        series built from them — to a permanent 0."""
        r = MetricsRegistry()
        t = r.timer("fast")
        for v in (0.4, 0.5, 0.6):
            t.observe(v)
        c = StatsCollector("tsd", host_tag=False)
        r.collect(c)
        p50 = next(ln for ln in c.lines if "percentile=50" in ln)
        assert 0.3 < float(p50.split()[2]) < 0.7

    def test_no_duplicate_timer_spellings_in_metrics(self):
        """Regression: the classic <name>.count/.sum_ms lines from
        collect() must dedup against the timer's summary family, not
        re-export as redundant untyped gauges."""
        r = MetricsRegistry()
        r.timer("dup.t").observe(2.0)
        c = StatsCollector("tsd", host_tag=False)
        r.collect(c)
        text = r.prometheus_text(extra_lines=c.lines)
        validate_exposition(text)
        assert "tsd_dup_t_ms_count" in text     # the summary's count
        assert "# TYPE tsd_dup_t_count" not in text
        assert "# TYPE tsd_dup_t_sum_ms" not in text
        assert "# TYPE tsd_dup_t gauge" not in text


# ---------------------------------------------------------------------------
# Trace spans
# ---------------------------------------------------------------------------

class TestTrace:
    def test_noop_when_inactive(self):
        assert obs_trace.current_span() is None
        with obs_trace.span("x") as sp:
            assert sp is None

    def test_tree_shape_and_timing(self):
        tr = obs_trace.Trace("q1", {"k": "v"})
        with obs_trace.activate(tr):
            with obs_trace.span("a", tag=1):
                with obs_trace.span("a.1"):
                    time.sleep(0.01)
            with obs_trace.span("b"):
                pass
        assert obs_trace.current_span() is None
        d = tr.to_dict()
        assert d["name"] == "query" and d["tags"]["q"] == "q1"
        names = [c["name"] for c in d["spans"]]
        assert names == ["a", "b"]
        assert d["spans"][0]["spans"][0]["name"] == "a.1"
        assert d["spans"][0]["ms"] >= d["spans"][0]["spans"][0]["ms"] >= 9
        assert d["ms"] >= d["spans"][0]["ms"]

    def test_timed_iter_accumulates_and_attaches(self):
        tr = obs_trace.Trace("q")
        with obs_trace.activate(tr):
            parent = obs_trace.current_span()

            def gen():
                yield 1
                time.sleep(0.01)
                yield 2

            out = list(obs_trace.timed_iter(gen(), parent, "shard.scan",
                                            {"shard": 0}))
        assert out == [1, 2]
        (sp,) = tr.root.children
        assert sp.name == "shard.scan"
        assert sp.tags == {"shard": 0, "rows": 2}
        assert sp.ms >= 9


def _spin(seconds):
    """Keep this thread on a core for ``seconds`` of its OWN clock, so
    for at least as long on the wall's: a loaded host stretches the
    wall time of a spin, never the CPU time its callers hold."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def _walk(d):
    yield d
    for c in d.get("spans", ()):
        yield from _walk(c)


def _span_counters(name):
    tags = {"span": name}
    return (METRICS.counter("query.span.wall_ms", tags).value,
            METRICS.counter("query.span.cpu_ms", tags).value)


class TestCpuTime:
    """CPU time beside wall time: every reading on one thread, so no
    assertion depends on how two threads split a lock."""

    def test_a_sleeping_span_reads_no_cpu(self):
        tr = obs_trace.Trace("q")
        with obs_trace.activate(tr):
            with obs_trace.span("cpu.sleeps"):
                time.sleep(0.05)
        sp, = tr.root.children
        assert sp.ms >= 49 and sp.cpu_ms < 5
        # The root slept too, and the tree says so on every span.
        assert tr.root.cpu_ms < 5 <= tr.root.ms
        d = tr.to_dict()
        assert all("cpu_ms" in n and "ms" in n for n in _walk(d))
        assert d["spans"][0]["cpu_ms"] == round(sp.cpu_ms, 3)

    def test_a_spinning_span_reads_its_wall_time(self):
        # How much of a spin's wall time its thread is on a core is the
        # host's to say (a loaded one takes the core away mid-spin), so
        # what is held is arithmetic: the span's CPU time is what the
        # thread's own clock read around the same spin, between a
        # reading taken inside the span and one taken around it.
        for _ in range(5):
            tr = obs_trace.Trace("q")
            with obs_trace.activate(tr):
                around = time.thread_time()
                with obs_trace.span("cpu.spins"):
                    inside = time.thread_time()
                    _spin(0.05)
                    inside = time.thread_time() - inside
                around = time.thread_time() - around
            sp, = tr.root.children
            assert sp.ms >= 49 and sp.cpu_ms <= sp.ms + 1
            assert 0 < inside * 1e3 <= sp.cpu_ms + 1e-3
            assert sp.cpu_ms <= around * 1e3 + 1e-3

    def test_timed_iter_keeps_the_cpu_of_its_pulls_alone(self):
        tr = obs_trace.Trace("q")
        with obs_trace.activate(tr):
            def gen():
                time.sleep(0.03)        # inside the first pull
                yield 1
                yield 2

            for _ in obs_trace.timed_iter(gen(), tr.root, "cpu.pulls"):
                _spin(0.03)             # between pulls: the caller's
        sp, = tr.root.children
        assert sp.tags["rows"] == 2
        # The sleep is the span's, the spinning is not.
        assert sp.ms >= 29 and sp.cpu_ms < 5
        assert tr.root.cpu_ms >= 20

    def test_a_closed_span_adds_to_the_two_tagged_counters(self):
        names = ("cpu.counted", "cpu.counted.child", "query")
        before = {n: _span_counters(n) for n in names}
        tr = obs_trace.Trace("q")
        with obs_trace.activate(tr):
            with obs_trace.span("cpu.counted"):
                with obs_trace.span("cpu.counted.child"):
                    time.sleep(0.01)
                _spin(0.01)
        outer, = tr.root.children
        inner, = outer.children
        for name, sp in zip(names, (outer, inner, tr.root)):
            wall, cpu = _span_counters(name)
            assert wall - before[name][0] == pytest.approx(sp.ms)
            assert cpu - before[name][1] == pytest.approx(sp.cpu_ms)
        coll = StatsCollector("tsd")
        METRICS.collect(coll)
        lines = [ln.split() for ln in coll.lines
                 if "span=cpu.counted.child" in ln]
        assert sorted(w[0] for w in lines) == ["tsd.query.span.cpu_ms",
                                               "tsd.query.span.wall_ms"]

    @pytest.mark.parametrize("tags", [{}, {"kind": "a"}])
    def test_timed_moves_the_cpu_counter_of_its_tags(self, tags):
        timer = METRICS.timer("cpu.test.phase", tags or None)
        cpu = METRICS.counter("cpu.test.phase.cpu_ms", tags or None)
        other = METRICS.counter("cpu.test.phase.cpu_ms", {"kind": "b"})
        n0, ms0, c0, o0 = timer.count, timer.total_ms, cpu.value, other.value
        with obs_trace.timed("cpu.test.phase", **tags):
            time.sleep(0.03)
        slept = cpu.value - c0
        assert timer.count == n0 + 1 and timer.total_ms - ms0 >= 29
        assert 0 <= slept < 5
        with obs_trace.timed("cpu.test.phase", **tags):
            _spin(0.03)
        assert timer.count == n0 + 2
        assert cpu.value - c0 - slept >= 10
        assert cpu.value - c0 <= timer.total_ms - ms0 + 2
        assert other.value == o0

    def test_timed_is_a_decorator_too(self):
        timer = METRICS.timer("cpu.test.decorated")
        cpu = METRICS.counter("cpu.test.decorated.cpu_ms")

        @obs_trace.timed("cpu.test.decorated")
        def work(x):
            _spin(0.01)
            return x + 1

        n0, c0 = timer.count, cpu.value
        assert [work(1), work(2)] == [2, 3]
        assert timer.count == n0 + 2 and cpu.value - c0 >= 5

    def test_a_timer_block_without_a_counter_reads_no_cpu_clock(
            self, monkeypatch):
        reg = MetricsRegistry()
        t = reg.timer("plain")
        calls = []
        real = time.thread_time_ns
        monkeypatch.setattr(time, "thread_time_ns",
                            lambda: calls.append(1) or real())
        with t.time():
            pass
        assert t.count == 1 and calls == []
        cpu = reg.counter("plain.cpu_ms")
        with t.time(cpu):
            pass
        assert t.count == 2 and len(calls) == 2 and cpu.value >= 0

    def test_the_write_side_blocks_keep_their_cpu(self, tmp_path):
        from opentsdb_tpu.server import wire
        from opentsdb_tpu.stats.livesketch import LiveSketches
        from opentsdb_tpu.storage.devstore import DeviceWindow

        names = ("ingest.parse", "ingest.batch", "devwindow.upload",
                 "sketch.fold")

        def read():
            return {n: (METRICS.timer(n).count,
                        METRICS.counter(n + ".cpu_ms").value)
                    for n in names}

        before = read()
        tsdb = TSDB(MemKVStore(), Config(auto_create_metrics=True,
                                         device_window=False))
        batch = wire.decode_puts(b"".join(
            b"put cpu.w %d %d host=h%d\n" % (BASE + i, i, i % 3)
            for i in range(300)))
        assert wire.ingest_batch(tsdb, batch)[0] == 300
        dw = DeviceWindow(staging_points=100, background=False)
        dw.append(b"\x00\x00\x01", b"sk",
                  BASE + np.arange(100, dtype=np.int64),
                  np.ones(100, np.float32))
        dw.flush()
        sk = LiveSketches(background=False, flush_points=1 << 30)
        sk.observe(b"\x00\x00\x01" + bytes(6), np.arange(50.0),
                   [(b"\x00\x00\x01", b"\x00\x00\x01", b"\x00\x00\x01")])
        sk.flush()
        after = read()
        for n in names:
            assert after[n][0] > before[n][0], n
            assert after[n][1] > before[n][1], n
        tsdb.shutdown()


class TestFaultDelaySpan:
    def test_wal_fsync_delay_lengthens_exactly_that_span(self, tmp_path):
        """The acceptance-criteria proof: an armed delay faultpoint on
        kv.wal.fsync stretches the wal.fsync span of a traced ingest —
        that span only, with a fault.delay child naming the site —
        and the next (disarmed) ingest's span is short again."""
        cfg = Config(auto_create_metrics=True, enable_sketches=False,
                     device_window=False, backend="cpu",
                     wal_path=str(tmp_path / "wal"))
        tsdb = TSDB(MemKVStore(wal_path=cfg.wal_path), cfg,
                    start_compaction_thread=False)
        try:
            faultpoints.arm("kv.wal.fsync", "delay", delay=0.15, count=1)
            tr = obs_trace.Trace("ingest")
            with obs_trace.activate(tr):
                tsdb.add_point("m.delay", BASE, 1, {"h": "a"})
            faultpoints.clear()
            d = tr.to_dict()
            fsync = [s for s in d.get("spans", [])
                     if s["name"] == "wal.fsync"]
            assert fsync, f"no wal.fsync span in {d}"
            assert fsync[0]["ms"] >= 140
            (child,) = fsync[0]["spans"]
            assert child["name"] == "fault.delay"
            assert child["tags"]["site"] == "kv.wal.fsync"
            # Every OTHER span stayed fast: the delay lengthened
            # exactly the matching stage.
            for s in d.get("spans", []):
                if s["name"] != "wal.fsync":
                    assert s["ms"] < 100
            tr2 = obs_trace.Trace("ingest2")
            with obs_trace.activate(tr2):
                tsdb.add_point("m.delay", BASE + 10, 2, {"h": "a"})
            fsync2 = [s for s in tr2.to_dict().get("spans", [])
                      if s["name"] == "wal.fsync"]
            assert fsync2 and fsync2[0]["ms"] < 100
            assert not fsync2[0].get("spans")
        finally:
            faultpoints.clear()
            tsdb.shutdown()


# ---------------------------------------------------------------------------
# Server: /q?trace=1, /metrics, /api/traces, slow-query log, selfmon
# ---------------------------------------------------------------------------

async def http_get(port, target):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {target} HTTP/1.1\r\nHost: x\r\n"
                 "Connection: close\r\n\r\n".encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    head, _, body = data.partition(b"\r\n\r\n")
    return int(head.split(b" ", 2)[1]), body


def run_async(server, coro_fn):
    async def main():
        await server.start()
        try:
            return await coro_fn(server.port)
        finally:
            server.selfmon.stop()
            server._pool.shutdown(wait=False)
            server._server.close()
            await server._server.wait_closed()
    return asyncio.run(main())


def make_server(tmp_path, shards=2, rollups=True, **cfg_over):
    wal_dir = tmp_path / "store"
    wal_dir.mkdir(exist_ok=True)
    kw = dict(auto_create_metrics=True, port=0, bind="127.0.0.1",
              enable_sketches=True, device_window=False, backend="cpu",
              rollup_catchup="sync", shards=shards,
              wal_path=str(wal_dir), enable_rollups=rollups)
    kw.update(cfg_over)
    cfg = Config(**kw)
    store = (ShardedKVStore(str(wal_dir), shards=shards) if shards > 1
             else MemKVStore(wal_path=str(wal_dir / "wal")))
    tsdb = TSDB(store, cfg, start_compaction_thread=False)
    rng = np.random.default_rng(3)
    for i in range(6):
        ts = BASE + np.arange(0, 2 * 86400, 60, dtype=np.int64)
        tsdb.add_batch("obs.metric", ts,
                       rng.normal(50, 10, len(ts)).astype(np.float32),
                       {"host": f"h{i}"})
    tsdb.checkpoint()  # spill + fold: rollup-served windows exist
    # A live tail AFTER the spill: guarantees raw stitching of dirty
    # windows on rollup-planned queries.
    tsdb.add_batch("obs.metric",
                   BASE + 2 * 86400 + np.arange(0, 1800, 60,
                                                dtype=np.int64),
                   np.ones(30, np.float32), {"host": "h0"})
    return TSDServer(tsdb), tsdb


def _span_names(d):
    out = {d["name"]}
    for c in d.get("spans", ()):
        out |= _span_names(c)
    return out


class TestServerTraces:
    def test_trace_covers_stages_and_sums_to_wall(self, tmp_path):
        server, tsdb = make_server(tmp_path)

        async def drive(port):
            q = (f"/q?start={BASE}&end={BASE + 2 * 86400 + 1800}"
                 "&m=sum:1h-avg:obs.metric&json&trace=1&nocache")
            return [await http_get(port, q) for _ in range(5)]

        got = run_async(server, drive)
        assert all(st == 200 for st, _body in got)
        outs = [json.loads(body) for _st, body in got]
        out = outs[0]
        assert out and out[0]["rollup"] in ("1h", "1d")
        tr = out[0]["trace"]
        names = _span_names(tr)
        # Stage coverage: planner pick, rollup read AND raw stitch
        # (dirty tail), per-shard fan-out, aggregate.
        for want in ("planner.pick", "rollup.read", "raw.stitch",
                     "shard.scan", "aggregate"):
            assert want in names, f"{want} missing from {sorted(names)}"
        picks = [s for s in tr["spans"] if s["name"] == "planner.pick"]
        assert picks[0]["tags"]["plan"] == out[0]["rollup"]
        # Fragment-cache outcome is visible on the stitch spans.
        stitches = [s for s in _walk(tr) if s["name"] == "raw.stitch"]
        assert stitches
        assert any(any(k.startswith("qcache_")
                       for k in s.get("tags", {}))
                   for s in stitches), stitches
        # Top-level stage durations tile the query wall time; the two
        # hops lie outside it. Never more than it (a rounding a span),
        # and 90% of it in the median request: where the host takes the
        # core away between two spans of one request is its to say.
        shares = []
        for tr in (o[0]["trace"] for o in outs):
            tops = [s["ms"] for s in tr["spans"]
                    if not s["name"].startswith("http.q.")]
            assert sum(tops) <= tr["ms"] + 1e-3 * len(tops)
            shares.append(sum(tops) / tr["ms"])
        assert sorted(shares)[len(shares) // 2] >= 0.9, shares

    def test_raw_trace_and_query_scan_delay(self, tmp_path):
        """Armed delay on the query.scan faultpoint stretches exactly
        the scan stage of a traced RAW query."""
        server, tsdb = make_server(tmp_path, rollups=False)
        faultpoints.arm("query.scan", "delay", delay=0.2, count=1)

        async def drive(port):
            q = (f"/q?start={BASE}&end={BASE + 86400}"
                 "&m=sum:obs.metric&json&trace=1&nocache")
            return await http_get(port, q)

        try:
            st, body = run_async(server, drive)
        finally:
            faultpoints.clear()
        assert st == 200
        tr = json.loads(body)[0]["trace"]
        by_name = {s["name"]: s for s in tr["spans"]}
        assert by_name["scan"]["ms"] >= 180
        assert "fault.delay" in _span_names(by_name["scan"])
        assert by_name["planner.pick"]["ms"] < 100
        assert "cached" in by_name["scan"]["tags"]

    def test_ring_bounded_and_served(self, tmp_path):
        server, tsdb = make_server(tmp_path, shards=1, rollups=False,
                                   trace_ring=2)

        async def drive(port):
            for i in range(3):
                st, _ = await http_get(
                    port, f"/q?start={BASE}&end={BASE + 3600 + i}"
                          "&m=sum:obs.metric&json&trace=1&nocache")
                assert st == 200
            return await http_get(port, "/api/traces")

        st, body = run_async(server, drive)
        assert st == 200
        recs = json.loads(body)
        assert len(recs) == 2  # bounded at Config.trace_ring
        for r in recs:
            assert r["trace"]["name"] == "query"
            assert r["plan"] == "raw"
            assert r["shards"] == 1 and r["replica"] is False
        assert server.trace_ring.recorded == 3

    def test_slow_query_log_and_flag(self, tmp_path, caplog):
        server, tsdb = make_server(tmp_path, shards=1, rollups=False,
                                   slow_query_ms=0.0001)

        async def drive(port):
            # No trace=1: threshold tracing alone must record it.
            st, _ = await http_get(
                port, f"/q?start={BASE}&end={BASE + 3600}"
                      "&m=sum:obs.metric&json&nocache")
            assert st == 200
            return await http_get(port, "/api/traces?slow=1")

        with caplog.at_level(logging.WARNING, "opentsdb_tpu.slowquery"):
            st, body = run_async(server, drive)
        recs = json.loads(body)
        assert recs and all(r["slow"] for r in recs)
        logged = [r for r in caplog.records
                  if r.name == "opentsdb_tpu.slowquery"]
        assert logged
        rec = json.loads(logged[0].getMessage())
        assert rec["q"].startswith("sum:")
        assert rec["wall_ms"] > 0 and rec["slow"] is True
        assert rec["trace"]["spans"]  # span tree attached

    def test_every_span_carries_cpu_and_every_root_its_two_hops(
            self, tmp_path):
        server, tsdb = make_server(tmp_path)
        subs = ("sum:1h-avg:obs.metric", "max:obs.metric{host=*}")

        async def drive(port):
            q = (f"/q?start={BASE}&end={BASE + 2 * 86400 + 1800}"
                 + "".join("&m=" + m for m in subs)
                 + "&json&trace=1&nocache")
            t0 = time.perf_counter()
            st, body = await http_get(port, q)
            wall = (time.perf_counter() - t0) * 1000.0
            return st, body, wall, await http_get(port, "/stats")

        st, body, wall, (st2, stats) = run_async(server, drive)
        assert st == 200 and st2 == 200
        trees = [r["trace"] for r in json.loads(body) if "trace" in r]
        assert [t["tags"]["q"] for t in trees] == list(subs)
        for tree in trees:
            assert tree["name"] == "query"
            for sp in _walk(tree):
                assert 0 <= sp["cpu_ms"] <= sp["ms"] + 1, sp
            hops = [s for s in _walk(tree)
                    if s["name"].startswith("http.q.")]
            assert sorted(s["name"] for s in hops) == ["http.q.queue",
                                                       "http.q.resume"]
            # On the root, outside its interval: the queue ends where
            # the root starts, the resume starts where it ends.
            assert [s["name"] for s in (tree["spans"][0],
                                        tree["spans"][-1])] \
                == ["http.q.queue", "http.q.resume"]
            queue, resume = tree["spans"][0], tree["spans"][-1]
            assert queue["ms"] >= 0 and resume["ms"] >= 0
            assert queue["cpu_ms"] == resume["cpu_ms"] == 0
            assert queue["t0"] <= tree["t0"] + 1e-3
            assert resume["t0"] >= tree["t0"] + tree["ms"] / 1e3 - 1e-3
        # The sub-queries run one after another inside the request.
        assert sum(t["ms"] + t["spans"][0]["ms"] + t["spans"][-1]["ms"]
                   for t in trees) <= wall
        lines = {}
        for ln in stats.decode().splitlines():
            w = ln.split()
            lines[w[0] + " " + " ".join(
                t for t in w[3:] if not t.startswith("host="))] = float(w[2])
        for name in ("query", "planner.pick", "http.q.queue",
                     "http.q.resume"):
            wall_ms = lines[f"tsd.query.span.wall_ms span={name}"]
            assert 0 <= lines[f"tsd.query.span.cpu_ms span={name}"] \
                <= wall_ms + 1
        assert lines["tsd.query.span.wall_ms span=query"] >= sum(
            t["ms"] for t in trees) - 0.01
        assert lines["tsd.process.cpu_ms "] > 100
        assert 0 < lines["tsd.http.q.encode.cpu_ms "] \
            <= lines["tsd.http.q.encode.sum_ms "] + 1

    def test_an_untraced_request_opens_no_span_and_moves_no_counter(
            self, tmp_path, monkeypatch):
        """Off means off: with no trace active ``span()`` is the shared
        no-op, and an untraced /q builds no Span and no Hops, so it
        takes none of their clock readings."""
        server, tsdb = make_server(tmp_path, shards=1, rollups=False)
        assert obs_trace._ACTIVE == 0
        assert obs_trace.span("x") is obs_trace._NOOP
        assert obs_trace.span("x", tag=1) is obs_trace.span("y")
        made = []
        start, hops = obs_trace.Span.start, obs_trace.Hops.__init__
        monkeypatch.setattr(
            obs_trace.Span, "start",
            lambda self: made.append(self.name) or start(self))
        monkeypatch.setattr(
            obs_trace.Hops, "__init__",
            lambda self: made.append("hops") or hops(self))
        q = (f"/q?start={BASE}&end={BASE + 3600}"
             "&m=sum:obs.metric&json&nocache")

        def counters():
            return {k[1][0][1]: _span_counters(k[1][0][1])
                    for k in list(METRICS._metrics)
                    if k[0] == "query.span.wall_ms"}

        async def drive(port):
            first = await http_get(port, q)
            untraced = len(made), counters()
            traced = await http_get(port, q + "&trace=1")
            return first, untraced, traced

        _span_counters("query")         # registered, so that it is held
        before = counters()
        enc = METRICS.counter("http.q.encode.cpu_ms").value
        (st, body), (n_made, after), (st2, _b) = run_async(server, drive)
        assert st == 200 and "trace" not in json.loads(body)[0]
        assert n_made == 0 and after == before
        # The guard can see: the traced twin of the request builds them
        # and moves the counters.
        assert st2 == 200 and "hops" in made and "query" in made
        assert _span_counters("query")[0] > before["query"][0]
        # ... and the encode's timed() block is on for both.
        assert METRICS.counter("http.q.encode.cpu_ms").value > enc

    def test_untraced_json_has_no_trace_key(self, tmp_path):
        server, tsdb = make_server(tmp_path, shards=1, rollups=False)

        async def drive(port):
            return await http_get(
                port, f"/q?start={BASE}&end={BASE + 3600}"
                      "&m=sum:obs.metric&json&nocache")

        st, body = run_async(server, drive)
        assert st == 200
        assert "trace" not in json.loads(body)[0]


class TestMetricsEndpoint:
    def test_metrics_valid_exposition_guard(self, tmp_path):
        """The tier-1 scraper guard: the merged registry + classic
        /stats exposition must stay parseable — duplicate families,
        samples before TYPE lines, or re-opened blocks fail here
        before a real Prometheus does."""
        server, tsdb = make_server(tmp_path)

        async def drive(port):
            # Exercise handlers first so handler timers have samples.
            await http_get(port, f"/q?start={BASE}&end={BASE + 3600}"
                                 "&m=sum:obs.metric&json&nocache&trace=1")
            await http_get(port, "/stats")
            return await http_get(port, "/metrics")

        st, body = run_async(server, drive)
        assert st == 200
        text = body.decode()
        n = validate_exposition(text)
        assert n > 50
        # The traced request's span counters, tagged by span name, and
        # the CPU counters beside the timers.
        assert "# TYPE tsd_query_span_cpu_ms counter" in text
        assert 'tsd_query_span_wall_ms{span="http.q.queue"}' in text
        assert "# TYPE tsd_http_q_encode_cpu_ms counter" in text
        assert 'tsd_checkpoint_phase_cpu_ms{phase="spill"}' in text
        assert "tsd_process_cpu_ms " in text
        assert "# TYPE tsd_wal_appends counter" in text
        assert "# TYPE tsd_http_handler_ms summary" in text
        assert 'endpoint="/q"' in text
        assert "# TYPE tsd_checkpoint_shard_spill_ms summary" in text

    def test_stats_gains_uptime_rss_and_shard_rows(self, tmp_path):
        server, tsdb = make_server(tmp_path)  # shards=2, live tail

        async def drive(port):
            return await http_get(port, "/stats")

        st, body = run_async(server, drive)
        lines = body.decode().splitlines()
        names = {}
        for ln in lines:
            names.setdefault(ln.split()[0], []).append(ln)
        assert "tsd.uptime_s" in names
        assert "tsd.process.rss_bytes" in names
        assert int(names["tsd.process.rss_bytes"][0].split()[2]) > 1 << 20
        rows = names["tsd.storage.memtable.rows"]
        assert len(rows) == 2  # one per shard
        assert {t for ln in rows for t in ln.split()
                if t.startswith("shard=")} == {"shard=0", "shard=1"}
        # The engine registry flows into the classic export too.
        assert "tsd.wal.fsync.count" in names
        assert "tsd.checkpoint.phase.count" in names


class TestSelfMonitor:
    def test_ingests_tsd_series_queryable_and_rollup_eligible(
            self, tmp_path):
        server, tsdb = make_server(tmp_path)

        async def drive(port):
            n = server.selfmon.run_once()
            assert n > 50
            n2 = server.selfmon.run_once()
            assert n2 >= n - 5  # second cycle sees >= the same lines
            st, body = await http_get(
                port, "/q?start=0&end=4102444800"
                      "&m=sum:tsd.datapoints.added&json&nocache")
            return st, body

        st, body = run_async(server, drive)
        assert st == 200
        out = json.loads(body)
        assert out and len(out[0]["dps"]) == 2  # both cycles, distinct ts
        vals = list(out[0]["dps"].values())
        assert vals[0] > 0
        # Rollup-eligible like any metric: the fold covers tsd.* rows.
        tsdb.checkpoint()
        uid = tsdb.metrics.get_id("tsd.datapoints.added")
        recs = tsdb.rollups.scan_records(3600, uid, 0, 2 ** 32 - 1)
        assert recs

    def test_timestamps_strictly_monotonic(self, tmp_path):
        server, tsdb = make_server(tmp_path, shards=1, rollups=False)

        async def drive(port):
            t1 = server.selfmon.run_once() and server.selfmon._last_ts
            t2 = server.selfmon.run_once() and server.selfmon._last_ts
            return t1, t2

        t1, t2 = run_async(server, drive)
        assert t2 > t1  # same-second cycles bump, never duplicate

    def test_reentrancy_guard(self, tmp_path):
        """A cycle triggered while a previous one is mid-ingest is
        refused — the one true recursion hazard of a store that
        monitors itself through its own instrumented write path."""
        cfg = Config(auto_create_metrics=True, enable_sketches=False,
                     device_window=False, backend="cpu")
        tsdb = TSDB(MemKVStore(), cfg, start_compaction_thread=False)
        from opentsdb_tpu.obs.selfmon import SelfMonitor
        inner_results = []
        mon = None

        def stats_fn():
            inner_results.append(mon.run_once())  # reentrant snapshot
            return [f"tsd.x {int(time.time())} 1"]

        mon = SelfMonitor(tsdb, stats_fn, 0.0)
        assert mon.run_once() == 1
        assert inner_results == [0]
        tsdb.shutdown()

    def test_read_only_replica_refuses(self, tmp_path):
        cfg = Config(auto_create_metrics=True, enable_sketches=False,
                     device_window=False, backend="cpu",
                     wal_path=str(tmp_path / "wal"))
        writer = TSDB(MemKVStore(wal_path=cfg.wal_path), cfg,
                      start_compaction_thread=False)
        writer.add_point("m.ro", BASE, 1, {"h": "a"})
        writer.checkpoint()
        replica = TSDB(MemKVStore(wal_path=cfg.wal_path,
                                  read_only=True),
                       Config(**{**cfg.__dict__}),
                       start_compaction_thread=False)
        from opentsdb_tpu.obs.selfmon import SelfMonitor
        mon = SelfMonitor(replica,
                          lambda: [f"tsd.x {int(time.time())} 1"], 0.0)
        assert mon.run_once() == 0
        replica.shutdown()
        writer.shutdown()


class TestFsckTimer:
    def test_run_fsck_records_duration_sample(self, tmp_path):
        """The fault-matrix canary's unit twin: every fsck run lands a
        tsd.fsck.duration observation in the process registry."""
        from opentsdb_tpu.tools.fsck import run_fsck
        cfg = Config(auto_create_metrics=True, enable_sketches=False,
                     device_window=False, backend="cpu")
        tsdb = TSDB(MemKVStore(), cfg, start_compaction_thread=False)
        tsdb.add_point("m.fsck", BASE, 1, {"h": "a"})
        t = METRICS.timer("fsck.duration")
        before = t.count
        rep = run_fsck(tsdb)
        assert rep.clean
        assert t.count == before + 1
        tsdb.shutdown()


class TestCliStats:
    def test_store_mode_lines(self, tmp_path, capsys):
        from opentsdb_tpu.tools.cli import main
        wal = str(tmp_path / "wal")
        data = tmp_path / "d.txt"
        data.write_text(f"cli.m {BASE} 1 a=b\ncli.m {BASE + 10} 2 a=b\n")
        assert main(["import", "--wal", wal, str(data)]) == 0
        capsys.readouterr()
        assert main(["stats", "--wal", wal, "--backend", "cpu"]) == 0
        out = capsys.readouterr().out
        lines = [ln for ln in out.splitlines() if ln]
        assert any(ln.startswith("tsd.datapoints.added ")
                   for ln in lines)
        assert any(ln.startswith("tsd.fsck.duration.count ")
                   for ln in lines)  # engine registry included
        # Every line is a well-formed stats line.
        for ln in lines:
            parts = ln.split()
            assert len(parts) >= 3 and parts[1].isdigit()
            float(parts[2])
            assert all("=" in t for t in parts[3:])

    def test_store_mode_metrics_valid(self, tmp_path, capsys):
        from opentsdb_tpu.tools.cli import main
        wal = str(tmp_path / "wal")
        data = tmp_path / "d.txt"
        data.write_text(f"cli.m2 {BASE} 1 a=b\n")
        assert main(["import", "--wal", wal, str(data)]) == 0
        capsys.readouterr()
        assert main(["stats", "--wal", wal, "--backend", "cpu",
                     "--metrics"]) == 0
        out = capsys.readouterr().out
        assert validate_exposition(out) > 10
        assert "tsd_datapoints_added" in out


class TestRingUnit:
    def test_capacity_and_counts(self):
        ring = TraceRing(2)
        tr = obs_trace.Trace("q")
        with obs_trace.activate(tr):
            pass
        for i in range(3):
            ring.add(make_record(f"q{i}", tr, "raw", False,
                                 slow_ms=0 if i < 2 else 1e9,
                                 shards=1, replica=False))
        assert len(ring) == 2
        assert ring.recorded == 3
        assert [r["q"] for r in ring.snapshot()] == ["q1", "q2"]

    def test_record_shape(self):
        tr = obs_trace.Trace("sum:m")
        with obs_trace.activate(tr):
            with obs_trace.span("scan"):
                time.sleep(0.002)
        rec = make_record("sum:m", tr, "1h", True, slow_ms=0.001,
                          shards=4, replica=True)
        assert rec["slow"] is True and rec["plan"] == "1h"
        assert rec["cached"] is True and rec["shards"] == 4
        assert rec["replica"] is True
        assert rec["wall_ms"] >= 2
        assert rec["trace"]["spans"][0]["name"] == "scan"
        json.dumps(rec)  # JSON-ready by construction
