"""Unified mesh execution plane (parallel/plan.py + parallel/compile.py).

The plane's contracts, each tested here:
- no mesh => compile_with_plan IS jax.jit (bit-identical programs);
- the plan cache answers repeat compiles (dashboards never rebuild);
- the sharded rollup window fold is BYTE-identical across mesh widths
  (series never split shards; the combine is an all_gather);
- the sharded dashboard reduction is byte-identical to the
  single-device control on integer-valued data (f32 partial sums of
  integers < 2^24 are exact under psum reassociation);
- the fused TSST4 stage runs pjit-sharded under a mesh and keeps its
  f32-tolerance contract vs the single-device fused leg;
- mesh.* observability exists in /stats and thresholds via
  `tsdb check --stats-metric`;
- the 2-process gloo leg (scripts/multihost_run.py --plane) proves
  both byte-parity batteries across a REAL process boundary.
"""

import asyncio
import json
import os

import jax
import numpy as np
import pytest

from opentsdb_tpu.ops import kernels
from opentsdb_tpu.parallel import compile as meshc
from opentsdb_tpu.parallel.mesh import HOST_AXIS, SERIES_AXIS, make_mesh
from opentsdb_tpu.parallel.plan import (
    ExecPlan,
    build_mesh,
    flatten_series_mesh,
)
from opentsdb_tpu.parallel.sharded import (
    pack_shards,
    sharded_downsample_group,
    sharded_window_fold,
)
from opentsdb_tpu.rollup import summary

RNG = np.random.default_rng(23)


def _series(n_series, span=72000, res=3600, integer=False):
    out = []
    for _ in range(n_series):
        n = int(RNG.integers(10, 300))
        ts = np.sort(RNG.choice(np.arange(span), size=n,
                                replace=False)).astype(np.int64)
        if integer:
            vals = RNG.integers(-500, 500, n).astype(np.float64)
        else:
            vals = RNG.normal(40.0, 9.0, n)
        out.append((ts, vals))
    return out


def _dense_integer_series(n_series, interval, num_buckets):
    """One point per bucket, integer-valued: the group stage's lerp
    fill never interpolates (no empty buckets), so every contribution
    is an exact small integer and f32 sums are exact under ANY
    reduction order — the arithmetic basis of the byte-parity
    batteries."""
    out = []
    for si in range(n_series):
        ts = (np.arange(num_buckets, dtype=np.int64) * interval
              + (si * 7) % interval)
        vals = RNG.integers(-500, 500, num_buckets).astype(np.float64)
        out.append((ts, vals))
    return out


class TestCompilePlane:
    def test_no_mesh_is_exactly_jit(self):
        def body(x, *, k):
            return (x * k).sum()

        plan = ExecPlan(name="test.body", static_argnames=("k",))
        fn = meshc.compile_with_plan(body, plan)
        x = RNG.normal(0, 1, 257).astype(np.float32)
        want = jax.jit(body, static_argnames=("k",))(x, k=3)
        got = fn(x, k=3)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()

    def test_cache_answers_repeat_compiles(self):
        def body2(x):
            return x + 1

        plan = ExecPlan(name="test.body2")
        h0, m0 = meshc._C_HIT.value, meshc._C_MISS.value
        a = meshc.compile_with_plan(body2, plan)
        b = meshc.compile_with_plan(body2, plan)
        assert a is b
        assert meshc._C_MISS.value == m0 + 1
        assert meshc._C_HIT.value == h0 + 1
        # Distinct statics are distinct cache entries.
        c = meshc.compile_with_plan(body2, plan, statics=(("y", 1),))
        assert c is not a

    def test_mesh_dispatch_metrics_move(self):
        mesh = make_mesh(4)
        series = _series(8, integer=True)
        ts, vals, sid, valid, sps = pack_shards(
            [((s[0]).astype(np.int64), s[1]) for s in series], 4)
        before = meshc._M_DISPATCH.count
        sharded_downsample_group(
            ts, vals, sid, valid, mesh=mesh, series_per_shard=sps,
            num_buckets=24, interval=3000, agg_down="sum",
            agg_group="sum")
        assert meshc._M_DISPATCH.count > before

    def test_rate_params_are_traced_not_static(self):
        """counter_max/reset_value are CLIENT-CONTROLLED query params:
        distinct values must reuse one compiled program (operands, not
        statics) — a per-value compile would let a hostile dashboard
        recompile-DoS the mesh leg."""
        mesh = make_mesh(4)
        series = _series(8, integer=True)
        ts, vals, sid, valid, sps = pack_shards(
            [((s[0]).astype(np.int64), s[1]) for s in series], 4)

        def run(cmax):
            return sharded_downsample_group(
                ts, vals, sid, valid, mesh=mesh, series_per_shard=sps,
                num_buckets=24, interval=3000, agg_down="avg",
                agg_group="sum", rate=True, counter=True,
                counter_max=cmax)

        run(2.0 ** 32)
        size0 = len(meshc._CACHE)
        for cmax in (123.0, 456.0, 789.5):
            run(cmax)
        assert len(meshc._CACHE) == size0, \
            "distinct counter_max minted new compile-cache entries"

    def test_registry_names_exist(self):
        from opentsdb_tpu.obs.registry import METRICS
        names = METRICS.names()
        for n in ("mesh.compile", "mesh.dispatch", "mesh.cache.hit",
                  "mesh.cache.miss", "mesh.devices"):
            assert n in names, n


class TestBuildMesh:
    def test_flat(self):
        m = build_mesh("4")
        assert m.axis_names == (SERIES_AXIS,)
        assert m.devices.size == 4

    def test_hybrid(self):
        m = build_mesh("2x4")
        assert m.axis_names == (HOST_AXIS, SERIES_AXIS)
        assert m.devices.shape == (2, 4)

    def test_flatten(self):
        m = build_mesh("2x4")
        f = flatten_series_mesh(m)
        assert f.axis_names == (SERIES_AXIS,)
        assert f.devices.size == 8
        assert flatten_series_mesh(f) is f

    def test_errors(self):
        with pytest.raises(ValueError):
            build_mesh("")
        with pytest.raises(ValueError):
            build_mesh("0")
        with pytest.raises(ValueError):
            build_mesh("9x9")

    def test_unknown_axis_or_style_rejected(self):
        with pytest.raises(ValueError):
            ExecPlan(name="x", axis="bogus")
        with pytest.raises(ValueError):
            ExecPlan(name="x", style="bogus")


class TestShardedWindowFold:
    @pytest.mark.parametrize("integer", [False, True])
    def test_byte_identical_across_mesh_widths(self, integer):
        series = _series(13, integer=integer)
        res = 3600
        a = summary.window_summaries_sharded(series, res, make_mesh(1))
        b = summary.window_summaries_sharded(series, res, make_mesh(4))
        for (wa, ra), (wb, rb) in zip(a, b):
            assert np.array_equal(wa, wb)
            assert ra.tobytes() == rb.tobytes()

    def test_matches_host_fold(self):
        series = _series(9)
        res = 3600
        got = summary.window_summaries_sharded(series, res,
                                               make_mesh(4))
        for (ts, vals), (wb, rb) in zip(series, got):
            wh, rh = summary.window_summaries(ts, vals, res)
            assert np.array_equal(wh, wb)
            np.testing.assert_array_equal(
                rh["count"].astype(np.float32), rb["count"])
            np.testing.assert_allclose(rh["sum"], rb["sum"],
                                       rtol=1e-6, atol=1e-4)
            for f in ("min", "max", "first", "last"):
                np.testing.assert_array_equal(
                    rh[f].astype(np.float32), rb[f])
            np.testing.assert_array_equal(rh["first_dt"],
                                          rb["first_dt"])
            np.testing.assert_array_equal(rh["last_dt"], rb["last_dt"])

    def test_long_span_timestamps_exact(self):
        """Offsets past 2^24 s (~194 days) must stay exact: the
        timestamp planes ride the f32 grid BITCAST, not cast — a cast
        rounds them by whole seconds, silently corrupting
        first_dt/last_dt on year-long folds."""
        res = 3600
        base = 400 * 86400  # offsets far past 2^24
        ts = np.asarray([base + 7, base + 3601, base + 3600 + 1801],
                        np.int64)
        vals = np.asarray([1.0, 2.0, 3.0])
        got = summary.window_summaries_sharded([(ts, vals)], res,
                                               make_mesh(2))
        wb, rb = got[0]
        wh, rh = summary.window_summaries(ts, vals, res)
        assert np.array_equal(wh, wb)
        np.testing.assert_array_equal(rh["first_dt"], rb["first_dt"])
        np.testing.assert_array_equal(rh["last_dt"], rb["last_dt"])

    def test_empty_and_all_empty(self):
        res = 600
        assert summary.window_summaries_sharded([], res,
                                                make_mesh(2)) == []
        got = summary.window_summaries_sharded(
            [(np.empty(0, np.int64), np.empty(0))], res, make_mesh(2))
        assert len(got) == 1 and len(got[0][0]) == 0

    def test_raw_kernel_grids(self):
        """The [D, 8, S_local, W] contract + first/last selection."""
        ts = np.array([[5, 100, 700, 1300]], np.int32)
        vals = np.array([[2.0, 7.0, 1.0, 9.0]], np.float32)
        sid = np.zeros((1, 4), np.int32)
        valid = np.ones((1, 4), bool)
        g = np.asarray(sharded_window_fold(
            ts, vals, sid, valid, mesh=make_mesh(1),
            series_per_shard=1, num_windows=3, res=600))
        assert g.shape == (1, 8, 1, 3)
        count, total, mn, mx, first, last = g[0, :6, 0, :]
        assert list(count) == [2, 1, 1]
        assert list(total) == [9.0, 1.0, 9.0]
        assert list(mn) == [2.0, 1.0, 9.0]
        assert list(mx) == [7.0, 1.0, 9.0]
        assert list(first) == [2.0, 1.0, 9.0]
        assert list(last) == [7.0, 1.0, 9.0]


class TestShardedReductionBytes:
    @pytest.mark.parametrize("agg", ["sum", "min", "max", "count"])
    def test_integer_battery_byte_identical(self, agg):
        """Mesh width cannot change a bit of the dashboard battery:
        dense integer-valued contributions make f32 partials exact
        under any psum reassociation; min/max/count are order-free
        outright."""
        interval, B = 3000, 24
        series = _dense_integer_series(16, interval, B)
        packed = [(s[0], s[1]) for s in series]

        def run(D):
            ts, vals, sid, valid, sps = pack_shards(packed, D)
            gv, gm = sharded_downsample_group(
                ts, vals, sid, valid, mesh=make_mesh(D),
                series_per_shard=sps, num_buckets=B,
                interval=interval, agg_down="sum", agg_group=agg)
            return np.asarray(gv), np.asarray(gm)

        gv1, gm1 = run(1)
        gv4, gm4 = run(4)
        assert np.array_equal(gm1, gm4)
        assert gv1.tobytes() == gv4.tobytes()
        # And the unsharded fused kernel agrees on the emitted grid.
        flat_ts = np.concatenate([s[0] for s in series]).astype(
            np.int32)
        flat_vals = np.concatenate(
            [s[1] for s in series]).astype(np.float32)
        flat_sid = np.concatenate(
            [np.full(len(s[0]), i, np.int32)
             for i, s in enumerate(series)])
        ref = kernels.downsample_group(
            flat_ts, flat_vals, flat_sid,
            np.ones(len(flat_ts), bool), num_series=len(series),
            num_buckets=B, interval=interval, agg_down="sum",
            agg_group=agg)
        refm = np.asarray(ref["group_mask"])
        assert np.array_equal(gm1, refm)
        np.testing.assert_array_equal(
            gv1[gm1], np.asarray(ref["group_values"])[refm])


def test_two_process_plane_byte_parity():
    """The committed multi-process proof for the execution plane: two
    gloo-joined OS processes, a flat 8-device series mesh spanning the
    process boundary, and the script's own assertions that the sharded
    rollup fold and the sharded query reduction are byte-identical to
    single-device controls."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "multihost_run.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    r = subprocess.run([sys.executable, script, "--plane"], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["mode"] == "plane"
    assert rec["process_count"] == 2
    assert rec["devices_global"] == 8
    assert rec["fold_shards_byte_checked_per_proc"] == 4
    assert rec["reduction_byte_identical"] is True


class TestServerObservability:
    def test_stats_and_check_cover_mesh_gauges(self, tmp_path, capsys):
        from tests.test_admission import (http_get, make_server,
                                          run_with_server)

        from opentsdb_tpu.tools.cli import main as cli_main
        server, tsdb = make_server(tmp_path, backend="tpu",
                                   mesh_shape="4")

        async def drive(port):
            sa, _, ba = await http_get(port, "/stats?json")
            sq, _, bq = await http_get(port, "/api/queries")
            loop = asyncio.get_running_loop()
            rc_ok = await loop.run_in_executor(None, cli_main, [
                "check", "-H", "127.0.0.1", "-p", str(port),
                "--stats-metric", "tsd.mesh.devices",
                "-x", "lt", "-c", "4"])
            rc_bad = await loop.run_in_executor(None, cli_main, [
                "check", "-H", "127.0.0.1", "-p", str(port),
                "--stats-metric", "tsd.mesh.devices",
                "-x", "lt", "-c", "5"])
            return (sa, ba), (sq, bq), rc_ok, rc_bad

        (sa, ba), (sq, bq), rc_ok, rc_bad = run_with_server(server,
                                                            drive)
        tsdb.shutdown()
        assert sa == 200 and sq == 200
        lines = json.loads(ba)
        assert any(ln.startswith("tsd.mesh.devices 4 ")
                   or ln.startswith("tsd.mesh.devices ")
                   and ln.split()[2] == "4" for ln in lines), \
            [ln for ln in lines if "mesh" in ln]
        assert any(ln.startswith("tsd.mesh.cache.size ")
                   for ln in lines)
        feed = json.loads(bq)
        assert feed["mesh"]["devices"] == 4
        assert "compile_cache" in feed["mesh"]
        assert rc_ok == 0
        assert rc_bad == 2


def test_two_process_served_deployment_mode():
    """The SERVED deployment-mode smoke across a real process boundary:
    two gloo-joined tsd-equivalent daemons (parallel/fleet.init_plane,
    the same bootstrap ``tsd --mesh-plane`` uses), each sharding its
    resident hot set over 4 local devices and self-checking over HTTP:
    advertised mesh width, resident gauges, resident-plan/scan parity,
    and a LIVE grow/shrink reshard with identical answers."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "multihost_run.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    r = subprocess.run([sys.executable, script, "--serve"], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["mode"] == "serve"
    assert rec["process_count"] == 2
    assert rec["devices_global"] == 8
    assert rec["width_advertised"] == 4
    assert rec["resident_query_parity"] is True
    assert rec["reshard_answers_identical"] is True


class TestServingMeshObservability:
    """The sharded resident hot set on the serving surfaces: /healthz
    width + resident block, /stats + /metrics gauges, /api/queries
    serving section, the /api/mesh/reshard admin endpoint, and
    ``tsdb check --stats-metric tsd.mesh.resident.points``."""

    def test_resident_gauges_and_reshard_endpoint(self, tmp_path):
        from tests.test_admission import (http_get, make_server,
                                          run_with_server)

        from opentsdb_tpu.tools.cli import main as cli_main
        server, tsdb = make_server(tmp_path, backend="tpu",
                                   devwindow_shards=3,
                                   device_window=True)
        BT = 1356998400
        rng = np.random.default_rng(5)
        for i in range(6):
            tsdb.add_batch("m.mesh", BT + np.arange(120) * 60,
                           rng.normal(10, 2, 120), {"h": f"x{i}"})
        tsdb.devwindow.flush()

        async def drive(port):
            sh, _, bh = await http_get(port, "/healthz")
            ss, _, bs = await http_get(port, "/stats?json")
            sm, _, bm = await http_get(port, "/metrics")
            sq, _, bq = await http_get(port, "/api/queries")
            # Nagios-style coverage of the new gauge, BEFORE the
            # reshard below empties the freshly staged shard set.
            loop = asyncio.get_running_loop()
            rc_ok = await loop.run_in_executor(None, cli_main, [
                "check", "-H", "127.0.0.1", "-p", str(port),
                "--stats-metric", "tsd.mesh.resident.points",
                "-x", "lt", "-c", "1"])
            rc_bad = await loop.run_in_executor(None, cli_main, [
                "check", "-H", "127.0.0.1", "-p", str(port),
                "--stats-metric", "tsd.mesh.resident.points",
                "-x", "lt", "-c", "999999999"])
            sr, _, br = await http_get(port,
                                       "/api/mesh/reshard?shards=2")
            sh2, _, bh2 = await http_get(port, "/healthz")
            sbad, _, _ = await http_get(port,
                                        "/api/mesh/reshard?shards=0")
            return ((sh, bh), (ss, bs), (sm, bm), (sq, bq), (sr, br),
                    (sh2, bh2), sbad, rc_ok, rc_bad)

        ((sh, bh), (ss, bs), (sm, bm), (sq, bq), (sr, br), (sh2, bh2),
         sbad, rc_ok, rc_bad) = run_with_server(server, drive)
        tsdb.shutdown()
        assert sh == ss == sm == sq == sr == sh2 == 200
        mesh = json.loads(bh)["mesh"]
        assert mesh["width"] == 3
        assert mesh["resident"]["shards"] == 3
        assert mesh["resident"]["points"] > 0
        assert mesh["resident"]["reshards"] == 0
        lines = json.loads(bs)
        pts = [ln for ln in lines
               if ln.startswith("tsd.mesh.resident.points ")]
        assert pts and float(pts[0].split()[2]) > 0, \
            [ln for ln in lines if "resident" in ln]
        assert any(ln.startswith("tsd.mesh.resident.shards ")
                   for ln in lines)
        assert any(ln.startswith("tsd.mesh.resident.reshard.count ")
                   for ln in lines)
        assert b"tsd_mesh_resident_points" in bm   # /metrics export
        serving = json.loads(bq)["mesh"]["serving"]
        assert serving["width"] == 3
        assert serving["resident"]["shards"] == 3
        # The live reshard admin endpoint: shrink 3 -> 2 committed...
        rr = json.loads(br)
        assert rr["n_shards"] == 2 and rr["generation"] == 1
        mesh2 = json.loads(bh2)["mesh"]
        assert mesh2["resident"]["shards"] == 2
        assert mesh2["resident"]["reshards"] == 1
        # ...and invalid widths refuse.
        assert sbad == 400
        assert rc_ok == 0
        assert rc_bad == 2

    def test_unsharded_daemon_refuses_reshard(self, tmp_path):
        from tests.test_admission import (http_get, make_server,
                                          run_with_server)
        server, tsdb = make_server(tmp_path)

        async def drive(port):
            s, _, b = await http_get(port,
                                     "/api/mesh/reshard?shards=2")
            sh, _, bh = await http_get(port, "/healthz")
            return s, b, json.loads(bh)

        s, b, health = run_with_server(server, drive)
        tsdb.shutdown()
        assert s == 400 and b"not sharded" in b
        # Non-mesh daemons keep a mesh-free healthz body.
        assert "mesh" not in health
