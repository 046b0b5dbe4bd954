"""The resident plan opened up: the resident.* spans under
planner.pick, one trace_id a request, the span starts, the profiler
annotations, the stage-cache / encode / snapshot instruments, kernels
named by their plan, and the untraced path left as it was."""

import asyncio
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS
from opentsdb_tpu.ops import kernels
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.server.tsd import TSDServer
from opentsdb_tpu.stats.collector import StatsCollector
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.utils.config import Config

BASE = 1356998400
SPAN = 4 * 3600
HOSTS = 64
RESIDENT = ["resident.columns", "resident.groups", "resident.stage",
            "resident.apply", "resident.wait", "resident.fetch",
            "resident.results"]


def make_tsdb(tmp_path, hosts=HOSTS, **cfg_over):
    """A store whose two metrics sit whole in the device window (the
    resident plan runs on the CPU's devices under backend "tpu")."""
    wal_dir = tmp_path / "store"
    wal_dir.mkdir(exist_ok=True)
    kw = dict(auto_create_metrics=True, port=0, bind="127.0.0.1",
              backend="tpu", device_window=True, enable_rollups=False,
              wal_path=str(wal_dir))
    kw.update(cfg_over)
    tsdb = TSDB(MemKVStore(wal_path=str(wal_dir / "wal")), Config(**kw),
                start_compaction_thread=False)
    rng = np.random.default_rng(11)
    ts = BASE + np.arange(0, SPAN, 10, dtype=np.int64)
    for metric in ("res.cpu", "res.mem"):
        for i in range(hosts):
            tsdb.add_batch(metric, ts,
                           rng.normal(50, 10, len(ts)).astype(np.float32),
                           {"host": f"h{i}"})
    return tsdb


async def http_get(port, target):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {target} HTTP/1.1\r\nHost: x\r\n"
                 "Connection: close\r\n\r\n".encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    head, _, body = data.partition(b"\r\n\r\n")
    return int(head.split(b" ", 2)[1]), body


def serve(tsdb, *targets):
    """Start a server on ``tsdb``, GET each target in turn (a callable
    among them is called in its turn, between two requests of the one
    server), stop."""
    server = TSDServer(tsdb)

    async def main():
        await server.start()
        try:
            return [t() if callable(t) else await http_get(server.port, t)
                    for t in targets]
        finally:
            server.selfmon.stop()
            server._pool.shutdown(wait=False)
            server._server.close()
            await server._server.wait_closed()
    return asyncio.run(main())


def q(start, end, *ms, trace=True):
    return (f"/q?start={start}&end={end}"
            + "".join(f"&m={m}" for m in ms)
            + "&json&nocache" + ("&trace=1" if trace else ""))


def stat(name, **tags):
    """The value /stats would give for this registry line."""
    c = StatsCollector("tsd")
    METRICS.collect(c)
    want = {f"{k}={v}" for k, v in tags.items()}
    for ln in c.lines:
        w = ln.split()
        if w[0] == "tsd." + name and want <= set(w[3:]):
            return float(w[2])
    raise KeyError(name)


def labels_moved():
    """A callable that gives, each time it is called, how far the
    counters query.results.labels.(kept, computed) moved since its
    last call (or since it was made)."""
    kept = METRICS.counter("query.results.labels.kept")
    computed = METRICS.counter("query.results.labels.computed")
    last = [kept.value, computed.value]

    def moved():
        was, last[:] = list(last), [kept.value, computed.value]
        return last[0] - was[0], last[1] - was[1]
    return moved


def walk(tree):
    yield tree
    for c in tree.get("spans", ()):
        yield from walk(c)


class TestResidentSpans:
    def test_seven_children_tile_planner_pick(self, tmp_path):
        tsdb = make_tsdb(tmp_path)
        m = "max:5m-max:res.cpu{host=*}"
        end = BASE + SPAN - 10
        # Cold (the programs compile), then new ranges (a stage each is
        # built by compiled programs), then the last range again (every
        # cache hits: microseconds, so only the order is held).
        starts = [BASE + 600 * n for n in (0, 1, 2, 3, 4, 5, 5)]
        again = len(starts) - 1
        got = serve(tsdb, *(q(start, end, m) for start in starts))
        shares = []
        for i, (st, body) in enumerate(got):
            assert st == 200
            out = json.loads(body)
            assert out and all(r["rollup"] == "resident" for r in out)
            tree = out[0]["trace"]
            (pick,) = [s for s in tree["spans"]
                       if s["name"] == "planner.pick"]
            kids = pick["spans"]
            assert [s["name"] for s in kids] == RESIDENT
            # Each start lies inside the parent's interval, in order
            # (a millisecond of slack: a span's start and its length
            # are read from two clocks).
            lo, hi = pick["t0"], pick["t0"] + pick["ms"] / 1000.0
            t0s = [s["t0"] for s in kids]
            assert t0s == sorted(t0s)
            assert all(lo - 1e-3 <= t <= hi + 1e-3 for t in t0s)
            assert tree["t0"] <= pick["t0"]
            total = sum(s["ms"] for s in kids)
            assert total <= pick["ms"] + 1e-3 * len(kids)
            if i < again:
                shares.append(total / pick["ms"])
            tags = {s["name"]: s.get("tags", {}) for s in kids}
            assert tags["resident.columns"]["chunks"] >= 1
            assert tags["resident.columns"]["points"] >= HOSTS * SPAN // 10
            assert tags["resident.groups"]["series"] == HOSTS
            assert tags["resident.groups"]["groups"] == HOSTS
            assert tags["resident.apply"]["g_out"] >= HOSTS
            assert tags["resident.fetch"]["bytes"] > 0
            assert tags["resident.results"]["results"] == HOSTS
            assert tags["resident.stage"]["hit"] is (i == again)
            assert tags["resident.groups"]["plan_hit"] is (i > 0)
            assert tags["resident.groups"]["mask_hit"] is (i > 0)
        # The seven tile their parent in the median request that built
        # a stage: where the host takes the core away between two spans
        # of one request is its to say.
        assert sorted(shares)[len(shares) // 2] >= 0.95, shares

    def test_sub_queries_of_one_request_share_one_trace_id(self, tmp_path):
        tsdb = make_tsdb(tmp_path)
        end = BASE + SPAN - 10
        (st, _), _, _, (_, ring) = serve(
            tsdb,
            q(BASE, end, "max:5m-max:res.cpu", "avg:5m-avg:res.mem"),
            q(BASE, end, "max:5m-max:res.cpu"),
            q(BASE, end, "sum:5m-sum:res.mem") + "&trace_parent=feedbeef",
            "/api/traces")
        assert st == 200
        recs = json.loads(ring)
        assert [r["q"] for r in recs] == [
            "max:5m-max:res.cpu", "avg:5m-avg:res.mem",
            "max:5m-max:res.cpu", "sum:5m-sum:res.mem"]
        ids = [r["trace_id"] for r in recs]
        assert ids[0] == ids[1] and len(ids[0]) == 16
        assert ids[2] != ids[0]
        assert ids[3] == "feedbeef"      # a hop keeps the router's id
        assert all("t0" in r["trace"] for r in recs)


class TestResultsLabelsKeptWithThePlan:
    def test_the_second_answer_takes_the_first_ones_labels(self, tmp_path):
        """The same group-by-host request twice: byte-equal bodies,
        equal to the raw plan's; the first builds the plan's labels, the
        second takes them; a series the directory gains (a generation
        bump) makes the next request build them again."""
        tsdb = make_tsdb(tmp_path)
        (tmp_path / "raw").mkdir()
        plain = make_tsdb(tmp_path / "raw", device_window=False)
        m = "max:5m-max:res.cpu{host=*}"
        end = BASE + SPAN - 10
        moved = labels_moved()

        def grow():
            tsdb.add_batch("res.cpu", np.array([BASE + 60], np.int64),
                           np.array([7.0], np.float32), {"host": "new"})

        ask = q(BASE, end, m, trace=False)
        ((st1, b1), d1, (st2, b2), d2, (st3, b3), d3, _, (st4, b4),
         d4) = serve(tsdb, ask, moved, ask, moved, q(BASE, end, m), moved,
                     grow, ask, moved)
        assert (st1, st2, st3, st4) == (200,) * 4
        assert b1 == b2
        assert d1 == (0, HOSTS) and d2 == (HOSTS, 0) and d3 == (HOSTS, 0)
        assert d4 == (0, HOSTS + 1)
        out = json.loads(b1)
        assert len(out) == HOSTS
        assert all(r["rollup"] == "resident" for r in out)
        ((st0, b0),) = serve(plain, ask)
        want = json.loads(b0)
        assert st0 == 200 and all(r["rollup"] == "raw" for r in want)
        # But for the plan's name the two bodies are one, byte for byte.
        assert b1.replace(b'"resident"', b'"raw"') == b0
        # Traced, the span still says how many results it built.
        tree = json.loads(b3)[0]["trace"]
        (res,) = [s for s in walk(tree) if s["name"] == "resident.results"]
        assert res["tags"]["results"] == HOSTS
        grown = json.loads(b4)
        assert len(grown) == HOSTS + 1
        (new,) = [r for r in grown if r["tags"]["host"] == "new"]
        assert new["dps"] == {str(BASE): 7.0}
        rest = [r for r in grown if r["tags"]["host"] != "new"]
        assert [r["dps"] for r in rest] == [r["dps"] for r in out]


class TestUntracedPathUnchanged:
    def test_no_device_sync_and_the_shared_noop(self, tmp_path,
                                                monkeypatch):
        tsdb = make_tsdb(tmp_path, hosts=4)
        ex = QueryExecutor(tsdb, backend="tpu")
        spec = QuerySpec("res.cpu", {"host": "*"}, "max",
                         downsample=(300, "max"))
        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: (calls.append(1), real(x))[1])
        assert obs_trace.span("resident.stage") is obs_trace._NOOP
        got = ex.run(spec, BASE, BASE + SPAN - 10)
        assert len(got) == 4 and calls == []
        trace = obs_trace.Trace("m")
        with obs_trace.activate(trace):
            traced = ex.run(spec, BASE, BASE + SPAN - 10)
        assert calls == [1]
        assert "resident.wait" in {s["name"] for s in walk(trace.to_dict())}
        for a, b in zip(got, traced):
            np.testing.assert_array_equal(a.values, b.values)
        assert obs_trace.span("resident.stage") is obs_trace._NOOP

    def test_a_process_that_opens_no_span_imports_no_profiler(self):
        code = (
            "import sys\n"
            "from opentsdb_tpu.obs import trace\n"
            "with trace.span('x') as sp:\n"
            "    assert sp is None\n"
            "assert trace.current_span() is None\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        res = subprocess.run([sys.executable, "-c", code], cwd=root,
                             capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr


class TestInstruments:
    def test_scripted_sequence_moves_each_by_the_expected_amount(
            self, tmp_path):
        tsdb = make_tsdb(tmp_path, hosts=4, enable_sketches=True,
                         tenant_accounting=True)
        names = ["devwindow.stage.miss", "devwindow.stage.hit",
                 "http.q.encode.count", "http.q.bytes"]

        def read():
            out = {n: stat(n) for n in names}
            for kind in ("sketch", "tenant"):
                try:
                    out[kind] = stat("checkpoint.snapshot.count", kind=kind)
                except KeyError:      # no checkpoint in this process yet
                    out[kind] = 0.0
            return out

        before = read()
        end = BASE + SPAN - 10
        got = serve(
            tsdb,
            q(BASE, end, "max:5m-max:res.cpu", trace=False),         # new
            q(BASE, end, "max:5m-max:res.cpu{host=*}", trace=False),  # same
            q(BASE + 600, end, "max:5m-max:res.cpu", "max:5m-max:res.mem",
              trace=False),                                # two new stages
            "/stats")
        assert [st for st, _ in got] == [200] * 4
        tsdb.checkpoint()
        after = read()
        delta = {k: after[k] - before[k] for k in before}
        assert delta["devwindow.stage.miss"] == 3
        assert delta["devwindow.stage.hit"] == 1
        assert delta["http.q.encode.count"] == 3
        assert delta["http.q.bytes"] == sum(len(b) for _, b in got[:3])
        assert delta["sketch"] == 1 and delta["tenant"] == 1
        # /stats lists the new lines under the names the layer files read.
        listed = {ln.split()[0] for ln in got[3][1].decode().splitlines()}
        assert {"tsd.devwindow.stage.miss", "tsd.devwindow.stage.hit",
                "tsd.devwindow.stage.evicted", "tsd.http.q.encode.sum_ms",
                "tsd.http.q.bytes"} <= listed

    def test_a_dead_data_version_counts_as_evicted(self, tmp_path):
        tsdb = make_tsdb(tmp_path, hosts=4)
        ex = QueryExecutor(tsdb, backend="tpu")
        spec = QuerySpec("res.cpu", {}, "max", downsample=(300, "max"))
        ex.run(spec, BASE, BASE + SPAN - 10)
        before = stat("devwindow.stage.evicted")
        tsdb.add_batch("res.cpu", np.array([BASE + SPAN - 5], np.int64),
                       np.array([1.0], np.float32), {"host": "h0"})
        ex.run(spec, BASE, BASE + SPAN - 10)
        assert stat("devwindow.stage.evicted") - before == 1

    def test_early_timer_checkpoint_is_a_clean_noop(self, tmp_path):
        """The compaction thread's timer can fire while __init__ still
        refills the device window: nothing to checkpoint, nothing
        logged, nothing raised."""
        tsdb = make_tsdb(tmp_path, hosts=2)
        half_built = TSDB.__new__(TSDB)
        half_built.store = tsdb.store
        half_built.config = tsdb.config
        assert half_built.checkpoint() == 0
        assert tsdb.checkpoint() >= 0


class TestProfilerTimeline:
    def test_host_events_of_a_traced_query(self, tmp_path):
        from jax.profiler import ProfileData

        tsdb = make_tsdb(tmp_path, hosts=4)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path / "prof"),
                                 profiler_options=opts)
        try:
            ((st, body),) = serve(
                tsdb, q(BASE, BASE + SPAN - 10, "max:5m-max:res.cpu"))
            tsdb.checkpoint()
        finally:
            jax.profiler.stop_trace()
        assert st == 200
        (path,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile"
                                / "*" / "*.xplane.pb"))
        seen = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.split(".")[0] in ("resident", "http",
                                                 "checkpoint", "query"):
                        seen.setdefault(ev.name, []).append(
                            dict(ev.stats))
        for name in ("query", "resident.stage", "resident.wait",
                     "http.q.encode", "checkpoint.snapshot",
                     "checkpoint.phase"):
            assert name in seen, sorted(seen)
        # The spans carry their request's id; a timer its tags.
        trace_id = seen["query"][0]["trace_id"]
        assert seen["resident.wait"][0]["trace_id"] == trace_id
        assert {s["phase"] for s in seen["checkpoint.phase"]} >= {"freeze"}
        assert {s["kind"] for s in seen["checkpoint.snapshot"]} == {
            "sketch", "tenant"}


class TestKernelsNamedByTheirPlan:
    def test_lowered_text_carries_the_plan_name(self):
        n, s, b = 256, 8, 16
        nseg = s * b + 1
        acc = [jnp.zeros(nseg, jnp.float32)] * 3 + [
            jnp.full(nseg, np.inf, jnp.float32),
            jnp.full(nseg, -np.inf, jnp.float32)]
        chunk = (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.float32),
                 jnp.zeros(n, jnp.int32), jnp.ones(n, bool))
        fold = kernels._chunk_fold.lower(
            (chunk, chunk), *acc, jnp.zeros((), jnp.int32),
            np.array([0, 100, 0, 1, 1, 0, 0, 0], np.int32), num_series=s,
            num_buckets=b, interval=10, need=kernels._needs("max"),
            block=n)
        text = fold.as_text(debug_info=True)
        assert "/window.chunk_fold/" in text
        assert "jit__chunk_fold" in text
        grid = jnp.zeros((s, b), jnp.float32)
        flag = jnp.zeros((s, b), bool)
        apply = kernels.window_moment_apply.lower(
            grid, flag, flag, flag, jnp.ones(s, bool),
            jnp.zeros(s, jnp.int32), num_groups=1, agg_group="max",
            g_out=1, b_out=b, wire_bf16=False)
        assert "/window.moment_apply/" in apply.as_text(debug_info=True)
        # In the compiled HLO the name is each operation's op_name.
        assert 'op_name="jit(_chunk_fold)/window.chunk_fold/' in \
            fold.compile().as_text()
