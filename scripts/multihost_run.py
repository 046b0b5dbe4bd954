"""Two-process jax.distributed proof of the DCN-side merge path.

The virtual 8-device dryrun exercises the hybrid ICI x DCN collective
PROGRAM, but in one process — nothing crosses a real process boundary.
This script is the missing leg (VERDICT r03 item 9): it forks itself
into TWO OS processes, each owning 4 virtual CPU devices (one "host"
row of the hybrid mesh), joins them with ``jax.distributed.initialize``
(the same bootstrap ``init_multihost`` wraps for real pods), and runs
the three hybrid kernels over a mesh whose HOST axis spans the process
boundary — so the level-2 merges (Chan psum, HLL register pmax,
t-digest all_gather+recompress) travel the real cross-process
collective transport, not shared memory.

Cases:
- exact two-level grouped downsample vs a single-process numpy/kernel
  oracle on identical deterministic data;
- UNEVEN shards: host 1 carries ~1/4 of host 0's real points (valid
  masks), so the merge weights differ per host;
- STRAGGLER: process 1 sleeps 2 s before entering the collective; the
  result must be identical and process 0's wall time shows it waited.

Run: python scripts/multihost_run.py    (parent forks both children)
Writes MULTIHOST_PROC.json to the repo root from process 0.

Every mode here is a gloo/CPU leg: the children are told apart by a CPU
flag (--xla_force_host_platform_device_count), are started with
JAX_PLATFORMS=cpu and pin the CPU platform before their first backend
touch, so on a chip host they never claim a chip (N processes cannot
share one host's chips — each would claim all of them and the second
would fail or hang). Bringing the multi-process fleet onto chips is
not what this script does.

``--serve`` runs the SERVED DEPLOYMENT MODE smoke (PR 18): the same
two gloo processes join the plane through ``parallel/fleet.init_plane``
(the exact bootstrap ``tsd --mesh-plane`` uses), each builds a TSDB
whose resident hot set is SHARDED over its 4 local devices
(storage/devshard.ShardedDeviceWindow), starts a real TSDServer on an
ephemeral port, and self-checks over HTTP that /healthz advertises the
mesh width the router weights by, /stats exports the
tsd.mesh.resident.* gauges, a dashboard query serves from the RESIDENT
plan with scan-path parity, and /api/mesh/reshard grows then shrinks
the shard fleet LIVE with byte-identical answers. Process 0 writes
MESH_SERVE_PROC.json.

Committed artifacts hold only run-stable fields (re-running the smoke
must not churn the repo); wall-clock facts (timestamps, straggler
waits, reshard latencies) go to an UNCOMMITTED ``*.local.json``
sidecar next to each artifact.

``--plane`` runs the MESH EXECUTION PLANE smoke instead (PR 15): the
same two gloo processes build a flat 8-device series mesh through
parallel/compile.compile_with_plan and prove that (a) the sharded
rollup window fold and (b) a sharded dashboard query reduction are
BYTE-IDENTICAL to single-device controls — the fold because a series
never splits across shards and its combine is an all_gather, the
reduction because the battery's values are integer-valued float32
(every partial sum exact below 2^24), so psum reassociation cannot
change a bit. Each process byte-checks its own addressable output
shards; process 0 additionally checks the replicated reduction row
against the single-device control and writes MESH_PLANE_PROC.json.

Parity: the reference's analog is many TSDs over one HBase cluster via
asynchbase RPC (src/core/TSDB.java:479-494); here the inter-node fabric
is the XLA collective runtime.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_PROC = 2
CHIPS_PER_PROC = 4
SPAN = 7200
INTERVAL = 300
B = SPAN // INTERVAL
N_PER_SHARD = 4096


def write_artifacts(name: str, stable: dict, volatile: dict) -> None:
    """Split the run record: ``name`` (committed) gets only fields that
    are identical across healthy re-runs; ``<name>.local.json``
    (gitignored) gets the wall-clock facts. Stdout still carries the
    merged dict for human eyes and the pytest wrappers."""
    with open(os.path.join(REPO, name), "w") as f:
        json.dump(stable, f, indent=2)
        f.write("\n")
    base = name[:-5] if name.endswith(".json") else name
    with open(os.path.join(REPO, base + ".local.json"), "w") as f:
        json.dump(volatile, f, indent=2)
        f.write("\n")
    print(json.dumps({**stable, **volatile}))


def synth(host: int, chip: int):
    """Deterministic per-shard data any process can reconstruct.
    Host 1 is UNEVEN: only a quarter of the points are real."""
    import numpy as np

    rng = np.random.default_rng(1000 + host * 8 + chip)
    n_real = N_PER_SHARD if host == 0 else N_PER_SHARD // 4
    ts = rng.integers(0, SPAN, N_PER_SHARD).astype(np.int32)
    vals = rng.normal(50.0 + host * 10 + chip, 5.0,
                      N_PER_SHARD).astype(np.float32)
    sid = np.zeros(N_PER_SHARD, np.int32)      # one series per shard
    valid = np.arange(N_PER_SHARD) < n_real
    return ts, vals, sid, valid


def synth_plane(shard: int):
    """Deterministic DENSE INTEGER-VALUED per-shard data for the
    plane's byte-parity legs: unique timestamps covering every
    downsample bucket (so the group stage's lerp fill never
    interpolates — every contribution is an exact integer) and values
    small enough that f32 partial sums stay exact under ANY psum
    reassociation (< 2^24). Byte-parity then follows from arithmetic,
    not from a lucky reduction order."""
    import numpy as np

    rng = np.random.default_rng(7000 + shard)
    # Unique timestamps, dense across the span: one per permutation
    # slot of the first N positions — with N_PER_SHARD=4096 over
    # SPAN=7200 every 300 s bucket holds many points.
    ts = rng.permutation(SPAN)[:N_PER_SHARD].astype(np.int32)
    vals = rng.integers(-500, 500, N_PER_SHARD).astype(np.float32)
    sid = np.zeros(N_PER_SHARD, np.int32)   # one series per shard
    valid = np.ones(N_PER_SHARD, bool)
    # Density invariant the exactness argument rests on.
    assert len(np.unique(ts // INTERVAL)) == SPAN // INTERVAL
    return ts, vals, sid, valid


def child_plane(process_id: int, coordinator: str) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=N_PROC,
                               process_id=process_id)
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from opentsdb_tpu.parallel.compile import (cache_info,
                                               set_mesh_devices)
    from opentsdb_tpu.parallel.mesh import SERIES_AXIS
    from opentsdb_tpu.parallel.sharded import (
        _sharded_window_fold_body,
        sharded_downsample_group,
        sharded_window_fold,
    )

    assert jax.process_count() == N_PROC
    rows = N_PROC * CHIPS_PER_PROC
    mesh = Mesh(np.asarray(jax.devices()), (SERIES_AXIS,))
    set_mesh_devices(rows)
    sharding = NamedSharding(mesh, P(SERIES_AXIS))

    def gmake(col: int, dtype):
        def cb(index):
            r = index[0]
            shards = [synth_plane(r0)[col] for r0 in range(rows)[r]]
            return np.stack(shards).astype(dtype)
        return jax.make_array_from_callback(
            (rows, N_PER_SHARD), sharding, cb)

    ts = gmake(0, np.int32)
    vals = gmake(1, np.float32)
    sid = gmake(2, np.int32)
    valid = gmake(3, bool)

    res = 600
    num_windows = SPAN // res
    # (a) Sharded rollup window fold over the REAL cross-process mesh.
    folded = sharded_window_fold(
        ts, vals, sid, valid, mesh=mesh, series_per_shard=1,
        num_windows=num_windows, res=res)
    folded.block_until_ready()
    # Single-device control: the same fold body, plain-jitted, on each
    # addressable shard's local data — BYTE-compared. (The body has no
    # collectives; the mesh combine is the out-spec concat itself.)
    body = jax.jit(functools.partial(
        _sharded_window_fold_body, series_per_shard=1,
        num_windows=num_windows, res=res))
    fold_shards_checked = 0
    for sh in folded.addressable_shards:
        d = sh.index[0].start or 0
        t0, v0, s0, m0 = synth_plane(d)
        want = np.asarray(body(t0[None], v0[None], s0[None], m0[None]))
        got = np.asarray(sh.data)
        assert got.tobytes() == want.tobytes(), \
            f"fold shard {d} diverges from single-device control"
        fold_shards_checked += 1
    assert fold_shards_checked == CHIPS_PER_PROC, fold_shards_checked

    # (b) Sharded dashboard reduction (psum combine) — integer-valued
    # data makes the f32 partial sums exact, so the replicated mesh
    # answer must equal the 1-device-mesh control byte for byte.
    B = SPAN // INTERVAL
    gv, gm = sharded_downsample_group(
        ts, vals, sid, valid, mesh=mesh, series_per_shard=1,
        num_buckets=B, interval=INTERVAL, agg_down="sum",
        agg_group="sum")
    gv.block_until_ready()
    if process_id != 0:
        return 0
    allsh = [synth_plane(d) for d in range(rows)]
    one = Mesh(np.asarray(jax.local_devices()[:1]), (SERIES_AXIS,))
    c_ts = np.concatenate([s[0] for s in allsh])[None]
    c_vals = np.concatenate([s[1] for s in allsh])[None]
    c_sid = np.concatenate(
        [np.full(N_PER_SHARD, d, np.int32) for d in range(rows)])[None]
    c_valid = np.concatenate([s[3] for s in allsh])[None]
    c_gv, c_gm = sharded_downsample_group(
        c_ts, c_vals, c_sid, c_valid, mesh=one, series_per_shard=rows,
        num_buckets=B, interval=INTERVAL, agg_down="sum",
        agg_group="sum")
    gv_h, gm_h = np.asarray(gv), np.asarray(gm)
    c_gv, c_gm = np.asarray(c_gv), np.asarray(c_gm)
    assert (gm_h == c_gm).all(), "reduction masks disagree"
    assert gv_h.tobytes() == c_gv.tobytes(), \
        "mesh reduction diverges from single-device control bytes"

    out = {
        "mode": "plane",
        "process_count": int(jax.process_count()),
        "devices_global": len(jax.devices()),
        "devices_local": jax.local_device_count(),
        "fold_shards_byte_checked_per_proc": fold_shards_checked,
        "fold_windows": int(num_windows),
        "reduction_buckets": int(B),
        "reduction_byte_identical": True,
    }
    volatile = {
        # How many programs the plane compiled follows the program, not
        # the run's health: a count that a PR's kernels move belongs to
        # the half no tier-1 run leaves changed in the tree.
        "compile_cache": cache_info(),
        "iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    write_artifacts("MESH_PLANE_PROC.json", out, volatile)
    return 0


def child(process_id: int, coordinator: str) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=N_PROC,
                               process_id=process_id)
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from opentsdb_tpu.parallel.mesh import HOST_AXIS, SERIES_AXIS
    from opentsdb_tpu.parallel.multihost import (
        hybrid_downsample_group,
        hybrid_hll_distinct,
        hybrid_tdigest,
        init_multihost,
        make_hybrid_mesh,
    )

    assert jax.process_count() == N_PROC, jax.process_count()
    assert init_multihost() is True     # already-initialized detection
    mesh = make_hybrid_mesh()           # 2 hosts x 4 local devices
    assert mesh.devices.shape == (N_PROC, CHIPS_PER_PROC)
    sharding = NamedSharding(mesh, P((HOST_AXIS, SERIES_AXIS)))

    rows = N_PROC * CHIPS_PER_PROC

    def gmake(col: int, dtype):
        def cb(index):
            r = index[0]
            shards = [synth(r0 // CHIPS_PER_PROC, r0 % CHIPS_PER_PROC)[col]
                      for r0 in range(rows)[r]]
            return np.stack(shards).astype(dtype)
        return jax.make_array_from_callback(
            (rows, N_PER_SHARD), sharding, cb)

    ts = gmake(0, np.int32)
    vals = gmake(1, np.float32)
    sid = gmake(2, np.int32)
    valid = gmake(3, bool)

    # STRAGGLER: process 1 arrives 2 s late; the collective must wait
    # and the answer must not change.
    if process_id == 1:
        time.sleep(2.0)
    t0 = time.perf_counter()
    gv_a, gm_a = hybrid_downsample_group(
        ts, vals, sid, valid, mesh=mesh, series_per_shard=1,
        num_buckets=B, interval=INTERVAL, agg_down="avg",
        agg_group="sum")
    gv_a.block_until_ready()
    wall = time.perf_counter() - t0

    est_a = hybrid_hll_distinct(ts, valid, mesh=mesh, p=14)
    qs = np.asarray([0.1, 0.5, 0.95], np.float32)
    tq_a = hybrid_tdigest(vals, valid, qs, mesh=mesh)
    tq_a.block_until_ready()

    if process_id != 0:
        # Participation in every collective is complete; the result
        # shards live on process 0's devices, so only it materializes.
        return 0
    gv, gm = np.asarray(gv_a), np.asarray(gm_a)
    est = float(est_a)
    tq = np.asarray(tq_a)

    # --- single-process oracle from the same deterministic data ---
    allsh = [synth(h, c) for h in range(N_PROC)
             for c in range(CHIPS_PER_PROC)]
    f_ts = np.concatenate([s[0][s[3]] for s in allsh])
    f_vals = np.concatenate([s[1][s[3]] for s in allsh])
    # per-bucket avg per shard-series, then sum over series
    want = np.zeros(B)
    wmask = np.zeros(B, bool)
    for s_ts, s_vals, _, s_valid in allsh:
        st, sv = s_ts[s_valid], s_vals[s_valid]
        for b in range(B):
            m = (st // INTERVAL) == b
            if m.any():
                want[b] += sv[m].mean()
                wmask[b] = True
    ds_err = float(np.abs(gv[wmask] - want[wmask]).max())
    assert (gm == wmask).all(), "bucket masks disagree"
    assert ds_err < 1e-3 * np.abs(want[wmask]).max(), ds_err

    exact_distinct = len(np.unique(f_ts))
    hll_rel = abs(est - exact_distinct) / exact_distinct
    assert hll_rel < 0.05, hll_rel

    exact_q = np.quantile(f_vals, qs)
    td_rel = float(np.abs((tq - exact_q) / exact_q).max())
    assert td_rel < 0.05, td_rel

    assert wall >= 1.5, \
        f"straggler not awaited: collective returned in {wall:.2f}s"

    out = {
        "process_count": int(jax.process_count()),
        "devices_global": len(jax.devices()),
        "devices_local": jax.local_device_count(),
        "mesh": [N_PROC, CHIPS_PER_PROC],
        "uneven_shards": {"host0_real": N_PER_SHARD,
                          "host1_real": N_PER_SHARD // 4},
        "downsample_group_max_abs_err": ds_err,
        "hll_rel_err": hll_rel,
        "tdigest_rel_err": td_rel,
        "straggler_delay_s": 2.0,
        "straggler_awaited": True,
    }
    volatile = {
        "straggler_observed_wall_s": round(wall, 2),
        "iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    write_artifacts("MULTIHOST_PROC.json", out, volatile)
    return 0


def child_serve(process_id: int, coordinator: str) -> int:
    """Served deployment mode: this process is one ``tsd --mesh-plane``
    member. It joins the plane through parallel/fleet (NOT a bespoke
    bootstrap — the same call the CLI makes), shards its resident hot
    set over its 4 local virtual devices, serves real HTTP, and proves
    the serving contracts end to end: advertised width, resident
    gauges, resident-plan parity with the scan path, and a LIVE
    grow/shrink reshard with identical answers throughout."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from opentsdb_tpu.parallel import fleet

    plane = fleet.init_plane(coordinator, N_PROC, process_id)
    import asyncio
    import tempfile

    import numpy as np

    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.server.tsd import TSDServer
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.utils.config import Config

    assert plane["process_count"] == N_PROC
    assert plane["devices_local"] == CHIPS_PER_PROC
    assert plane["devices_global"] == N_PROC * CHIPS_PER_PROC
    work = tempfile.mkdtemp(prefix=f"meshserve{process_id}-")
    wal = os.path.join(work, "wal")
    cfg = Config(auto_create_metrics=True, wal_path=wal,
                 backend="tpu", device_window=True,
                 devwindow_shards=plane["devices_local"],
                 mesh_plane=coordinator, mesh_plane_procs=N_PROC,
                 mesh_plane_id=process_id,
                 enable_sketches=False, enable_rollups=False,
                 port=0, bind="127.0.0.1")
    tsdb = TSDB(MemKVStore(wal_path=wal), cfg,
                start_compaction_thread=False)
    dw = tsdb.devwindow
    assert hasattr(dw, "shard_of"), "resident hot set is not sharded"
    assert dw.n_shards == CHIPS_PER_PROC

    # Each process ingests ITS slice of the fleet corpus — in a real
    # deployment the router's width-weighted fan-out is what lands a
    # series on exactly one daemon.
    base = 1356998400
    metric = "mesh.serve.cpu"
    rng = np.random.default_rng(31 + process_id)
    for i in range(8):
        ts = base + np.arange(0, SPAN, 60, dtype=np.int64)
        vals = rng.integers(0, 500, len(ts)).astype(np.float64)
        tsdb.add_batch(metric, ts, vals, {"host": f"p{process_id}h{i}"})

    server = TSDServer(tsdb)

    async def http_get(port, target):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       port)
        writer.write(f"GET {target} HTTP/1.1\r\nHost: x\r\n"
                     "Connection: close\r\n\r\n".encode())
        await writer.drain()
        data = await reader.read()
        writer.close()
        head, _, body = data.partition(b"\r\n\r\n")
        return int(head.split(b" ", 2)[1]), body

    qtarget = (f"/q?start={base}&end={base + SPAN}"
               f"&m=sum:10m-avg:{metric}&json&nocache")

    async def drive(port):
        # Width advertisement: the router weights fan-out by this.
        st, body = await http_get(port, "/healthz")
        assert st == 200, body
        mesh = json.loads(body)["mesh"]
        assert mesh["width"] == CHIPS_PER_PROC, mesh
        assert mesh["plane"]["process_count"] == N_PROC, mesh
        assert mesh["resident"]["shards"] == CHIPS_PER_PROC, mesh

        # Resident-plan query, then the SAME HTTP path with the hot
        # set detached (scan) — answers must agree.
        hits0 = dw.window_hits
        st, body = await http_get(port, qtarget)
        assert st == 200, body
        served = json.loads(body)
        assert dw.window_hits > hits0, "query did not hit resident set"
        tsdb.devwindow = None
        try:
            st, body = await http_get(port, qtarget)
        finally:
            tsdb.devwindow = dw
        assert st == 200, body
        scanned = json.loads(body)
        assert len(served) == len(scanned) == 1

        def close(a, b):
            assert a["dps"].keys() == b["dps"].keys()
            for k in a["dps"]:
                assert abs(a["dps"][k] - b["dps"][k]) <= 1e-4 * max(
                    1.0, abs(b["dps"][k])), k
        close(served[0], scanned[0])

        # Resident gauges on the wire.
        st, body = await http_get(port, "/stats?json")
        assert st == 200
        stats = [ln for ln in json.loads(body)
                 if "tsd.mesh.resident." in ln]
        pts = [ln for ln in stats if "tsd.mesh.resident.points" in ln]
        assert pts and float(pts[0].split()[2]) > 0, stats

        # LIVE reshard: grow to 8 logical shards, shrink back to 2 —
        # the same query must return the same answer at every width.
        for n in (8, 2):
            st, body = await http_get(port,
                                      f"/api/mesh/reshard?shards={n}")
            assert st == 200, body
            r = json.loads(body)
            assert r["n_shards"] == n, r
            st, body = await http_get(port, qtarget)
            assert st == 200, body
            close(json.loads(body)[0], served[0])
        st, body = await http_get(port, "/healthz")
        res = json.loads(body)["mesh"]["resident"]
        assert res["reshards"] == 2 and res["shards"] == 2, res
        return {"reshard_ms": res.get("last_reshard_ms", 0.0)}

    async def amain():
        await server.start()
        try:
            return await drive(server.port)
        finally:
            server._pool.shutdown(wait=False)
            server._server.close()
            await server._server.wait_closed()

    r = asyncio.run(amain())
    tsdb.shutdown()
    if process_id != 0:
        return 0
    out = {
        "mode": "serve",
        "process_count": N_PROC,
        "devices_local": CHIPS_PER_PROC,
        "devices_global": N_PROC * CHIPS_PER_PROC,
        "width_advertised": CHIPS_PER_PROC,
        "resident_query_parity": True,
        "live_reshard_grow_shrink": [8, 2],
        "reshard_answers_identical": True,
        "stats_gauge": "tsd.mesh.resident.points",
    }
    volatile = {
        "last_reshard_ms": r["reshard_ms"],
        "iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    write_artifacts("MESH_SERVE_PROC.json", out, volatile)
    return 0


def main() -> int:
    role = os.environ.get("MH_PROCESS_ID")
    mode = os.environ.get("MH_MODE") or (
        "plane" if "--plane" in sys.argv[1:]
        else "serve" if "--serve" in sys.argv[1:] else "hybrid")
    if role is not None:
        if mode == "plane":
            return child_plane(int(role), os.environ["MH_COORDINATOR"])
        if mode == "serve":
            return child_serve(int(role), os.environ["MH_COORDINATOR"])
        return child(int(role), os.environ["MH_COORDINATOR"])
    # parent: pick a free port, fork both children
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env_base = dict(os.environ)
    env_base["XLA_FLAGS"] = (
        env_base.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={CHIPS_PER_PROC}"
    ).strip()
    env_base["JAX_PLATFORMS"] = "cpu"      # gloo/CPU leg, by name
    env_base["MH_COORDINATOR"] = coord
    env_base["MH_MODE"] = mode
    procs = []
    for pid in range(N_PROC):
        env = dict(env_base)
        env["MH_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    rc = 0
    for pid, p in enumerate(procs):
        try:
            # Below the pytest wrapper's own 560 s ceiling, so the
            # per-process TIMEOUT diagnostics fire before pytest kills
            # the whole tree.
            out, err = p.communicate(timeout=480)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            print(f"proc {pid}: TIMEOUT", file=sys.stderr)
            rc = 1
            continue
        if p.returncode != 0:
            rc = 1
            print(f"proc {pid} rc={p.returncode}\n--- stderr ---\n"
                  f"{err[-3000:]}", file=sys.stderr)
        elif pid == 0:
            print(out.strip())
    return rc


if __name__ == "__main__":
    sys.exit(main())
