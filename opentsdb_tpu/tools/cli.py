"""The ``tsdb``-style command-line interface.

Parity: reference tsdb.in subcommand dispatch (:50-82) + src/tools/*:
  tsd       the network daemon              (TSDMain.java)
  import    bulk text loader                (TextImporter.java)
  query     CLI query runner                (CliQuery.java)
  scan      raw row dumper, --import/--delete  (DumpSeries.java)
  fsck      table consistency checker, --fix   (Fsck.java)
  uid       UID admin: grep/assign/rename/fsck (UidManager.java)
  mkmetric  shortcut for `uid assign metrics`  (tsdb.in:62-64)

Storage note: the embedded engine lives in this process; offline tools
operate on the same data by replaying the daemon's WAL (pass --wal). Run
``tsd`` with --wal to make data durable and tool-accessible. A chip
belongs to one process at a time: beside a live daemon run the tools with
``--backend cpu`` (and ``--read-only``), or they claim the daemon's chip.
"""

from __future__ import annotations

import argparse
import gzip
import json
import logging
import os
import sys
import threading
import time

import numpy as np

from opentsdb_tpu.core import codec, tags as tags_mod
from opentsdb_tpu.core.errors import NoSuchUniqueName
from opentsdb_tpu.core.tsdb import FAMILY, TSDB
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.utils.config import Config
from opentsdb_tpu.utils.timeparse import parse_date

LOG = logging.getLogger("opentsdb_tpu.tools")


def common_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--table", default="tsdb")
    p.add_argument("--uidtable", default="tsdb-uid")
    p.add_argument("--wal", default=None, help="WAL file path (shared state)")
    p.add_argument("--shards", type=int, default=0,
                   help="partition storage into N series-sharded KVStore "
                        "shards; with N > 1 the --wal path is the store "
                        "DIRECTORY (shard-<i>/ subdirs + SHARDS.json). "
                        "0 = auto: sharded iff --wal already holds a "
                        "SHARDS.json manifest (its count wins); an "
                        "explicit N that disagrees with the manifest is "
                        "a hard error")
    p.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    p.add_argument("--sstable-codec", default="none",
                   choices=["none", "tsst4"],
                   help="write-side sstable format: 'tsst4' spills "
                        "compressed columnar blocks (delta-of-delta "
                        "timestamps + XOR floats; opentsdb_tpu/"
                        "compress/). Read side sniffs per file, so "
                        "existing v1-v3 generations keep serving and "
                        "compaction re-encodes as they merge")
    p.add_argument("--rollups", action="store_true",
                   help="maintain the materialized rollup tier "
                        "(opentsdb_tpu/rollup/): per-series 1h/1d "
                        "summaries computed at checkpoint spill and "
                        "served by the query planner for window-aligned "
                        "downsamples. Writer daemons with --wal only; "
                        "a stale/missing tier degrades to raw scans")
    p.add_argument("--rollup-resolutions", default=None,
                   help="comma-separated rollup window sizes in seconds "
                        "(ascending, each a multiple of 3600 dividing "
                        "the next; default 3600,86400)")
    p.add_argument("--sketch-byte-budget", type=int, default=None,
                   help="accuracy-budgeted sketch allocation (sketch/"
                        "budget.py): spend this many summary bytes "
                        "across the rollup resolutions (kind + size "
                        "per resolution, Storyboard-style) instead of "
                        "the uniform sketch_min_res cutoff; `tsdb "
                        "sketch-plan` previews the allocation")
    p.add_argument("--auto-metric", action="store_true",
                   help="automatically create metric UIDs (ingest)")
    p.add_argument("--read-only", action="store_true",
                   help="open the WAL as a read-only replica of a "
                        "(possibly live) writer daemon: serve reads "
                        "over the same store files without the "
                        "single-writer lock; all mutations refused. "
                        "A replica daemon polls the writer's durable "
                        "state every --checkpoint-interval seconds "
                        "(default 5 when read-only)")
    p.add_argument("--verbose", action="store_true")


# TSDBs opened by the current main() invocation; the dispatcher shuts
# down any the command left open (early return or exception), so no
# code path can leak the WAL's single-writer flock for the rest of an
# embedding process. Thread-local (an embedder may run main() from
# several threads) and swept only above the invocation's own
# high-water mark (nested main() calls must not close their caller's
# store).
_OPEN_TSDBS = threading.local()


def _open_list() -> list:
    lst = getattr(_OPEN_TSDBS, "lst", None)
    if lst is None:
        lst = _OPEN_TSDBS.lst = []
    return lst


def _require_window_fits(cfg: Config,
                         block_points: int | None = None) -> None:
    """Refuse to boot with a device-window budget the serving device
    cannot hold beside one query's stage at the largest grid the
    resident plan serves (storage/devstore.py ``require_fits``): found
    here, in a sentence with both numbers, and not by an allocation
    failing in the middle of the refill. A sharded window
    (--devwindow-shards) is checked by the share of the budget its
    fullest device holds. A device that states no limit (a CPU) is not
    checked. On a device that states one, the block cache of the fused
    plan is bounded here too (``Config.devblock_points``): by half of
    what is left beside the window and a query's stage, or by the
    argv's ``block_points`` where that is less."""
    import jax

    from opentsdb_tpu.ops import kernels
    from opentsdb_tpu.storage import devstore
    from opentsdb_tpu.utils import jaxenv

    points = cfg.device_window_points
    if cfg.devwindow_shards > 0:
        devices = min(jax.local_device_count(), cfg.devwindow_shards)
        points = (points // cfg.devwindow_shards
                  * -(-cfg.devwindow_shards // devices))
    mem = jaxenv.device_memory()
    try:
        devstore.require_fits(
            points, cfg.device_window_staging,
            kernels.stage_accumulator_bytes(),
            mem and mem["bytes_limit"])
    except ValueError as e:
        raise SystemExit(f"tsd: --device-window-points: {e}") from None
    if not mem or cfg.devblock_points <= 0:
        return
    # The device block cache (compress/devcache.py) may grow to half of
    # what the device has left, the other half staying free for the
    # stages' own arrays; --device-block-points can only lower that.
    # What it allocates is what the store's blocks need, up to this.
    from opentsdb_tpu.compress import devcache
    left = (mem["bytes_limit"]
            - devstore.window_bytes(points, cfg.device_window_staging)
            - kernels.stage_accumulator_bytes())
    most = max(left // 2 // devcache.POINT_BYTES, 1)
    cfg.devblock_points = most if block_points is None \
        else min(block_points, most)
    LOG.info("device block cache: up to %d points (%d bytes of the "
             "device's %d)", cfg.devblock_points,
             cfg.devblock_points * devcache.POINT_BYTES,
             mem["bytes_limit"])


def make_tsdb(args, start_thread: bool = False) -> TSDB:
    if getattr(args, "backend", None) == "cpu":
        # Pin the JAX platform BEFORE anything initializes the default
        # backend: with --backend cpu nothing may claim the chip (it
        # belongs to one process at a time — this is how an offline
        # tool runs beside a live daemon).
        import jax

        jax.config.update("jax_platforms", "cpu")
    cfg = Config(
        table=args.table, uidtable=args.uidtable, wal_path=args.wal,
        backend=args.backend, auto_create_metrics=args.auto_metric,
        sstable_codec=getattr(args, "sstable_codec", "none"))
    if getattr(args, "sketch_byte_budget", None) is not None:
        cfg.sketch_byte_budget = int(args.sketch_byte_budget)
    if getattr(args, "rollups", False):
        cfg.enable_rollups = True
    if getattr(args, "rollup_resolutions", None):
        # An explicit layout implies the tier: without this, a writer
        # invoked with --rollup-resolutions but not --rollups would
        # spill a rollup-backed store without folding (skipping the
        # auto-adopt below too) and leave summaries silently stale.
        cfg.enable_rollups = True
        cfg.rollup_resolutions = tuple(
            int(r) for r in args.rollup_resolutions.split(","))
    elif args.wal:
        # Auto-adopt an existing rollup tier (the SHARDS.json
        # precedent): ANY writer that spills a rollup-backed store
        # without folding would leave summaries silently stale — so
        # offline tools (import/fsck/scan --delete) must keep the tier
        # current whenever its state file exists, flag or no flag. The
        # state file's own layout wins over Config defaults.
        from opentsdb_tpu.rollup.tier import STATE_NAME, RollupTier
        for sp in (os.path.join(args.wal, STATE_NAME),
                   args.wal + ".rollup.json"):
            if os.path.exists(sp):
                cfg.enable_rollups = True
                # Unreadable/foreign state: tier opens and rebuilds.
                RollupTier.adopt_config(sp, cfg)
                break
    # The device-resident hot window serves long-lived query traffic;
    # one-shot tools (import/scan/fsck/uid/query) would only pay its
    # warm-up scan and uploads to throw them away on exit.
    cfg.device_window = hasattr(args, "port")
    if hasattr(args, "port"):
        cfg.port = args.port
        cfg.bind = args.bind
        cfg.staticroot = args.staticroot
        cfg.cachedir = args.cachedir
        cfg.flush_interval = args.flush_interval
        cfg.checkpoint_interval = getattr(args, "checkpoint_interval", 0.0)
        cfg.wal_group_ms = getattr(args, "wal_group_ms", 0.0)
        if getattr(args, "read_only", False) \
                and not cfg.checkpoint_interval \
                and getattr(args, "role", "writer") != "replica":
            # A legacy --read-only daemon that never polls would serve
            # a permanently frozen snapshot; the timer drives
            # refresh_replica() (core/compaction.py). Serve-tier
            # replicas (--role replica) are excluded: the WalTailer is
            # their ONLY refresh driver — a second concurrent driver
            # would race the rollup tier's refresh and do catch-up
            # work the tailer's lag clock never sees.
            cfg.checkpoint_interval = 5.0
        cfg.mesh_devices = getattr(args, "mesh_devices", 0)
        cfg.mesh_shape = getattr(args, "mesh", "") or ""
        cfg.expert_parallel = getattr(args, "expert_parallel", False)
        cfg.mesh_plane = getattr(args, "mesh_plane", "") or ""
        cfg.mesh_plane_procs = getattr(args, "mesh_plane_procs", 1)
        cfg.mesh_plane_id = getattr(args, "mesh_plane_id", 0)
        cfg.devwindow_shards = getattr(args, "devwindow_shards", 0)
        budget = getattr(args, "device_window_points", 0)
        if budget < 0:
            raise SystemExit("--device-window-points must not be negative")
        if budget:
            cfg.device_window_points = budget
            # A chunk is what eviction drops at a time: keep the budget
            # at 64 chunks, as the defaults have it (1 << 26 over
            # 1 << 20), and never a larger upload than the default's.
            cfg.device_window_staging = min(cfg.device_window_staging,
                                            max(budget // 64, 1024))
        blocks = getattr(args, "device_block_points", None)
        if blocks is not None and blocks < 0:
            raise SystemExit("--device-block-points must not be negative")
        if blocks is not None:
            cfg.devblock_points = blocks
        cfg.rollup_device_fold = getattr(args, "rollup_device_fold",
                                         False)
        if cfg.mesh_plane:
            # Join the serving mesh BEFORE the storage engine touches a
            # jax backend (TSDB construction warms the device window):
            # the distributed client and the CPU collectives transport
            # latch at backend init. A failed join is a boot failure —
            # a daemon asked to be part of a mesh must not silently
            # serve as a singleton.
            from opentsdb_tpu.parallel.fleet import init_plane
            plane = init_plane(cfg.mesh_plane, cfg.mesh_plane_procs,
                               cfg.mesh_plane_id)
            if cfg.devwindow_shards == 0:
                # Default the resident hot set to one shard per local
                # device — the deployment mode's whole point.
                cfg.devwindow_shards = max(1, plane["devices_local"])
        # The daemon's first backend touch: after the plane join (which
        # must precede it), before the storage engine warms the device
        # window. Exits when --backend tpu would serve from a non-TPU.
        from opentsdb_tpu.utils import jaxenv
        LOG.info("jax compile cache: %s", jaxenv.setup_compile_cache())
        jaxenv.require_serving_device(cfg.backend)
        if cfg.backend != "cpu" and not getattr(args, "read_only", False):
            # (A read-only daemon keeps no device window: core/tsdb.py.)
            _require_window_fits(cfg, blocks)
        cfg.slow_query_ms = getattr(args, "slow_query_ms", 0.0)
        cfg.selfmon_interval_s = getattr(args, "selfmon_interval", 0.0)
        cfg.trace_sample_n = getattr(args, "trace_sample_n", 0)
        # Serve tier (opentsdb_tpu/serve/): staleness contract +
        # admission knobs ride the daemon config.
        cfg.role = getattr(args, "role", "writer")
        cfg.max_staleness_ms = getattr(args, "max_staleness_ms", 0.0)
        cfg.tail_interval_s = getattr(args, "tail_interval", 0.25)
        cfg.query_max_inflight = getattr(args, "query_max_inflight", 0)
        cfg.query_rate = getattr(args, "query_rate", 0.0)
        cfg.query_burst = getattr(args, "query_burst", 8.0)
        cfg.ingest_rate = getattr(args, "ingest_rate", 0.0)
        cfg.ingest_queue_points = getattr(args, "ingest_queue_points",
                                          0)
        # Tenant cardinality control plane (opentsdb_tpu/tenant/).
        if getattr(args, "no_tenant_accounting", False):
            cfg.tenant_accounting = False
        cfg.tenant_max_series = getattr(args, "tenant_max_series", 0)
        cfg.tenant_global_max_series = getattr(
            args, "tenant_global_max_series", 0)
        cfg.tenant_limit_mode = getattr(args, "tenant_limit_mode",
                                        "enforce")
        cfg.tenant_overrides = tuple(
            getattr(args, "tenant_override", []) or ())
        cfg.tenant_exact_cutoff = getattr(args, "tenant_exact_cutoff",
                                          4096)
    read_only = getattr(args, "read_only", False)
    shards = getattr(args, "shards", 0) or 0
    from opentsdb_tpu.storage.sharded import manifest_path

    manifest = manifest_path(args.wal) if args.wal else None
    dir_store = bool(shards > 1
                     or (manifest and os.path.exists(manifest)))
    # Cluster write tier (opentsdb_tpu/cluster/): --cluster adopts (or
    # creates, at epoch 1) the EPOCH.json next to the WAL. Writers
    # stamp their epoch into WAL segments and fence every mutation
    # against promotion bumps; replicas just remember the path so
    # /promote can take over.
    epoch_path = None
    writer_epoch = None
    epoch_guard = None
    if getattr(args, "cluster", False) and args.wal:
        from opentsdb_tpu.cluster import epoch as _ep

        cfg.cluster = True
        cfg.cluster_owner = (getattr(args, "cluster_owner", None)
                             or f"{os.uname().nodename}:{os.getpid()}")
        epoch_path = _ep.epoch_path_for_wal(args.wal, is_dir=dir_store)
        if not read_only:
            cur, _owner = _ep.read_epoch(epoch_path)
            if cur == 0:
                _ep.write_epoch(epoch_path, 1, cfg.cluster_owner)
                cur = 1
            else:
                # A writer BOOT claims ownership with a fresh bump,
                # never by adopting the persisted epoch: a restarted
                # deposed writer adopting epoch N while the promoted
                # replica (also at N) still serves would put two
                # unfenced writers at the SAME epoch — no guard,
                # header, or replay fence could tell them apart.
                # Bumping makes every boot a new ownership
                # generation: if another writer is live, exactly one
                # of the two survives the fence (the booter), loudly,
                # instead of both surviving silently. Restart the old
                # daemon with --role replica if the promoted writer
                # should keep the store.
                cur = _ep.bump_epoch(epoch_path, cfg.cluster_owner,
                                     expect=cur)
            writer_epoch = cur
            epoch_guard = _ep.EpochGuard(
                epoch_path, cur,
                interval_s=cfg.epoch_check_interval_s)
    if dir_store:
        from opentsdb_tpu.storage.sharded import ShardedKVStore

        # An explicit --shards (1 included) is passed through so a
        # disagreement with the manifest is the promised hard error;
        # only the 0 default defers to the manifest count.
        store = ShardedKVStore(args.wal,
                               shards=shards if shards >= 1 else None,
                               data_table=args.table,
                               read_only=read_only,
                               writer_epoch=writer_epoch,
                               epoch_guard=epoch_guard)
        cfg.shards = store.shard_count
    else:
        store = MemKVStore(wal_path=args.wal, read_only=read_only,
                           writer_epoch=writer_epoch,
                           epoch_guard=epoch_guard)
    tsdb = TSDB(store, cfg, start_compaction_thread=start_thread)
    tsdb.cluster_epoch_path = epoch_path
    lst = _open_list()
    lst.append(tsdb)
    # Shutdown (idempotent, always reached via the main() sweep or the
    # command's own cleanup) removes the entry, so embedders that call
    # make_tsdb() directly don't pin every store they ever opened.
    def _dereg(t=tsdb, lst=lst):
        if t in lst:
            lst.remove(t)
    tsdb._deregister = _dereg
    return tsdb


# ---------------------------------------------------------------------------
# tsd
# ---------------------------------------------------------------------------

def cmd_tsd(args) -> int:
    import asyncio

    from opentsdb_tpu.server.tsd import TSDServer

    role = getattr(args, "role", "writer")
    if role == "router":
        return _cmd_router(args)
    if role == "replica":
        # A serve-tier replica IS a read-only daemon, plus the WAL
        # tailer and the staleness contract.
        args.read_only = True
        if not getattr(args, "max_staleness_ms", 0.0):
            # The contract defaults ON for the replica role: a serve
            # tier without a staleness bound is just the old poller.
            args.max_staleness_ms = 5000.0
    tsdb = make_tsdb(args, start_thread=True)
    # Replayed WAL/sstable state is in place: freeze it out of cycle
    # collection (utils/gctune.py has the measured motivation — gen2
    # passes over a multi-million-object memtable cost ~40% of
    # sustained ingest).
    from opentsdb_tpu.utils.gctune import tune_for_ingest
    tune_for_ingest()
    server = TSDServer(tsdb)
    if role == "replica":
        from opentsdb_tpu.serve.tailer import WalTailer

        tailer = WalTailer(tsdb)
        server.attach_tailer(tailer)
        tailer.start()

    async def main():
        await server.start()
        # Graceful shutdown on SIGTERM/SIGINT (the reference registers
        # a JVM shutdown hook, TSDMain.java): flush + close the WAL and
        # stop threads instead of dying with buffered state.
        import signal

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, server.request_shutdown)
            except (NotImplementedError, RuntimeError):
                pass  # non-Unix event loop
        print(f"Ready to serve on {tsdb.config.bind}:{server.port}",
              flush=True)
        await server.serve_forever()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        tsdb.shutdown()
    return 0


def _cmd_router(args) -> int:
    """``tsd --role router``: the storage-free front door
    (serve/router.py). Imports neither jax nor the storage engine —
    a router restart is sub-second by construction."""
    import asyncio

    from opentsdb_tpu.serve.router import RouterServer

    backends = tuple(u.strip() for u in
                     (getattr(args, "backends", "") or "").split(",")
                     if u.strip())
    writers = tuple(u.strip() for u in
                    (getattr(args, "writers", "") or "").split(",")
                    if u.strip())
    cfg = Config(
        port=args.port, bind=args.bind, role="router",
        router_backends=backends,
        writer_url=getattr(args, "writer_url", None) or None,
        router_deadline_ms=getattr(args, "router_deadline_ms",
                                   10_000.0),
        router_retries=getattr(args, "router_retries", 2),
        router_hedge_ms=getattr(args, "router_hedge_ms", 0.0),
        probe_interval_s=getattr(args, "probe_interval", 1.0),
        router_eject_after=getattr(args, "router_eject_after", 3),
        query_max_inflight=getattr(args, "query_max_inflight", 0),
        query_rate=getattr(args, "query_rate", 0.0),
        query_burst=getattr(args, "query_burst", 8.0),
        ingest_rate=getattr(args, "ingest_rate", 0.0),
        ingest_queue_points=getattr(args, "ingest_queue_points", 0),
        # Cluster write tier: automatic failover grace, multi-writer
        # ownership, and the router-side result cache.
        writer_grace_ms=getattr(args, "writer_grace_ms", 0.0),
        router_writers=writers,
        cluster_map=getattr(args, "cluster_map", None) or None,
        cluster_slots=getattr(args, "cluster_slots", 64),
        router_rcache=getattr(args, "router_rcache", 0),
        router_rcache_ms=getattr(args, "router_rcache_ms", 1000.0))
    server = RouterServer(cfg)

    async def main():
        await server.start()
        import signal

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, server.request_shutdown)
            except (NotImplementedError, RuntimeError):
                pass
        print(f"Ready to serve on {cfg.bind}:{server.port}",
              flush=True)
        await server.serve_forever()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        pass
    return 0


# ---------------------------------------------------------------------------
# import
# ---------------------------------------------------------------------------

def cmd_import(args) -> int:
    tsdb = make_tsdb(args)
    total = 0
    t_start = time.time()
    for path in args.files:
        t0 = time.time()
        n = _import_file(tsdb, path)
        dt = max(time.time() - t0, 1e-9)
        LOG.info("Processed %s in %d ms, %d data points (%.1f points/s)",
                 path, dt * 1000, n, n / dt)
        print(f"{path}: {n} points in {dt:.2f}s ({n / dt:,.0f} points/s)")
        total += n
    dt = max(time.time() - t_start, 1e-9)
    print(f"Total: imported {total} data points in {dt:.2f}s "
          f"({total / dt:,.0f} points/s)")
    tsdb.shutdown()
    return 0


def _import_file(tsdb: TSDB, path: str) -> int:
    """Bulk-load one (optionally gzipped) text file.

    Buffers points per series and flushes through the columnar batch path
    — the TPU-era analog of TextImporter's setBatchImport(true).
    """
    opener = gzip.open if path.endswith(".gz") else open
    series: dict[tuple, tuple[list, list, list]] = {}
    n = 0
    with opener(path, "rt") as f:
        for lineno, line in enumerate(f, 1):
            words = tags_mod.split_string(line.strip())
            if not words:
                continue
            try:
                metric = words[0]
                ts = tags_mod.parse_long(words[1])
                value = words[2]
                tag_map: dict[str, str] = {}
                for t in words[3:]:
                    tags_mod.parse(tag_map, t)
                key = (metric, tuple(sorted(tag_map.items())))
                tsl, vl, il, fl = series.setdefault(key, ([], [], [], []))
                tsl.append(ts)
                # int-vs-float sniffed per point, like the reference's
                # Tags.looksLikeInteger in TextImporter/PutDataPointRpc.
                # Integers parse exactly (int64) — float64 would corrupt
                # counters above 2^53.
                if tags_mod.looks_like_integer(value):
                    iv = tags_mod.parse_long(value)
                    fl.append(False)
                    il.append(iv)
                    vl.append(float(iv))
                else:
                    fl.append(True)
                    il.append(0)
                    vl.append(float(value))
                n += 1
            except ValueError as e:
                raise ValueError(
                    f"Invalid data at line {lineno}: {line!r}: {e}") from e
    for (metric, tag_items), (tsl, vl, il, fl) in series.items():
        ts_arr = np.asarray(tsl, np.int64)
        order = np.argsort(ts_arr, kind="stable")
        # Durable: unlike the reference's setDurable(false) batch mode,
        # the WAL is this engine's only persistence AND the shared state
        # offline tools replay — skipping it would lose the import. The
        # batch path already writes just one compacted cell per row-hour.
        tsdb.add_batch(metric, ts_arr[order],
                       np.asarray(vl, np.float64)[order], dict(tag_items),
                       is_float=np.asarray(fl, bool)[order],
                       int_values=np.asarray(il, np.int64)[order])
    return n


# ---------------------------------------------------------------------------
# query
# ---------------------------------------------------------------------------

def cmd_query(args) -> int:
    """CLI grammar parity with CliQuery.parseCommandLineQuery (:191-243):
    query START-DATE [END-DATE] FUNC [rate] [downsample N FUNC] metric
    [tag=value...]"""
    from opentsdb_tpu.query.aggregators import Aggregators
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec

    tsdb = make_tsdb(args)
    words = args.args
    start = parse_date(words.pop(0))
    end = int(time.time())
    if words and words[0] not in Aggregators.available():
        end = parse_date(words.pop(0))
    agg = words.pop(0)
    rate = False
    downsample = None
    if words and words[0] == "rate":
        rate = True
        words.pop(0)
    if words and words[0] == "downsample":
        words.pop(0)
        interval = int(words.pop(0))
        downsample = (interval, words.pop(0))
    metric = words.pop(0)
    tag_map: dict[str, str] = {}
    for t in words:
        tags_mod.parse(tag_map, t)

    ex = QueryExecutor(tsdb)
    spec = QuerySpec(metric, tag_map, aggregator=agg, rate=rate,
                     downsample=downsample)
    results = ex.run(spec, start, end)
    if getattr(args, "graph", None):
        # CliQuery's --graph wrote gnuplot data files (:222-243); the
        # matplotlib pipeline writes the finished PNG directly.
        from opentsdb_tpu.graph.plot import Plot

        plot = Plot(start, end)
        for r in results:
            label = r.metric + ("{" + ",".join(
                f"{k}={v}" for k, v in sorted(r.tags.items())) + "}"
                if r.tags else "")
            plot.add(label, r.timestamps, r.values)
        path = args.graph + ".png"
        with open(path, "wb") as f:
            f.write(plot.render())
        print(f"wrote {path}")
    else:
        for r in results:
            tag_str = " ".join(
                f"{k}={v}" for k, v in sorted(r.tags.items()))
            for ts, v in zip(r.timestamps, r.values):
                vs = (str(int(v)) if float(v).is_integer()
                      else repr(float(v)))
                print(f"{r.metric} {int(ts)} {vs} {tag_str}".rstrip())
    tsdb.shutdown()
    return 0


# ---------------------------------------------------------------------------
# scan
# ---------------------------------------------------------------------------

def cmd_scan(args) -> int:
    """Raw storage dumper (DumpSeries.java): decodes rows/cells; --import
    emits re-importable lines; --delete removes what it prints."""
    tsdb = make_tsdb(args)
    words = list(args.args)
    start = parse_date(words.pop(0))
    end = int(time.time())
    if words and not words[0][0].isalpha():
        end = parse_date(words.pop(0))
    metric = words.pop(0)
    tag_map: dict[str, str] = {}
    for t in words:
        tags_mod.parse(tag_map, t)

    metric_uid = tsdb.metrics.get_id(metric)
    start_key = metric_uid + int(codec.base_time(start)).to_bytes(4, "big")
    stop_key = metric_uid + int(
        min(codec.base_time(end) + 3600, 0xFFFFFFFF)).to_bytes(4, "big")
    for cells in tsdb.store.scan(tsdb.table, start_key, stop_key,
                                 family=FAMILY):
        key = cells[0].key
        parsed = codec.parse_row_key(key)
        named = {tsdb.tagk.get_name(k): tsdb.tagv.get_name(v)
                 for k, v in parsed.tag_uids}
        if tag_map and any(named.get(k) != v for k, v in tag_map.items()):
            continue
        tag_str = " ".join(f"{k}={v}" for k, v in sorted(named.items()))
        if not args.importfmt:
            print(f"{key.hex()} {metric} {parsed.base_time} {tag_str}")
        for cell in cells:
            for c in codec.explode_cell(cell.qualifier, cell.value):
                ts = parsed.base_time + c.delta
                val = c.decode()
                vs = (str(val) if isinstance(val, int)
                      else repr(float(val)))
                if args.importfmt:
                    print(f"{metric} {ts} {vs} {tag_str}".rstrip())
                else:
                    kind = "float" if c.flags & 0x8 else "long"
                    print(f"  [{c.qualifier.hex()}]\t[{c.value.hex()}]\t"
                          f"{ts}\t{kind}\t{vs}")
        if args.delete:
            tsdb.store.delete_row(tsdb.table, key)
    tsdb.shutdown()
    return 0


# ---------------------------------------------------------------------------
# fsck
# ---------------------------------------------------------------------------

def cmd_fsck(args) -> int:
    """Table consistency check (Fsck.java): validates qualifiers, values,
    meta bytes, duplicate/out-of-order points; --fix rewrites rows. The
    actual checks live in tools/fsck.py (run_fsck) so the fault
    harness's "fsck clean" invariant runs the operator tool verbatim.

    ``--expect-clean`` makes "any error found" exit 2 even under --fix
    (which otherwise reports success after salvaging) — the crash
    matrix / CI contract: a store that NEEDED fixing after a crash is
    a failed invariant, not a success."""
    from opentsdb_tpu.tools.fsck import run_fsck

    tsdb = make_tsdb(args)
    t0 = time.time()
    rep = run_fsck(tsdb, fix=args.fix, log=print)
    print(f"sstables: {rep.bloomed} with series blooms, {rep.plain} "
          f"bloomless/legacy, {rep.bloom_misses} bloom false negatives")
    if rep.format_counts:
        mix = " ".join(f"v{fmt}={n}" for fmt, n in
                       sorted(rep.format_counts.items()))
        print(f"sstable formats: {mix}")
    if rep.blocks:
        per = " ".join(f"{name}={n}" for name, n in
                       sorted(rep.codec_counts.items()))
        print(f"compressed blocks: {rep.blocks} audited ({per}), "
              f"{rep.codec_errors} codec errors")
    dt = max(time.time() - t0, 1e-9)
    print(f"{rep.kvs} KVs (in {rep.rows} rows) analyzed in "
          f"{dt * 1000:.0f}ms (~{rep.kvs / dt:.0f} KV/s)")
    print(f"Found {rep.errors} errors." + (f" Fixed {rep.fixed} rows."
                                           if args.fix else ""))
    tsdb.shutdown()
    if getattr(args, "expect_clean", False) and rep.errors:
        return 2
    return 1 if rep.errors and not args.fix else 0


# ---------------------------------------------------------------------------
# uid / mkmetric
# ---------------------------------------------------------------------------

def cmd_uid(args) -> int:
    """UID admin (UidManager.java): grep / assign / rename / fsck /
    lookups. Always shuts the store down on exit — early returns that
    skipped shutdown leaked the WAL's single-writer lock for the rest
    of the process."""
    tsdb = make_tsdb(args)
    try:
        return _cmd_uid(tsdb, args)
    finally:
        tsdb.shutdown()


def _cmd_uid(tsdb: TSDB, args) -> int:
    words = list(args.args)
    if not words:
        print("usage: uid [grep|assign|rename|fsck|KIND NAME|ID]",
              file=sys.stderr)
        return 2
    uids = {"metrics": tsdb.metrics, "tagk": tsdb.tagk, "tagv": tsdb.tagv}
    cmd = words[0]
    if cmd == "grep":
        words.pop(0)
        kinds = list(uids)
        if words and words[0] in uids:
            kinds = [words.pop(0)]
        import re as _re
        pattern = _re.compile(words[0] if words else ".")
        found = False
        for kind in kinds:
            for name in uids[kind].suggest("", limit=1 << 30):
                if pattern.search(name):
                    print(f"{kind} {name}: "
                          f"{uids[kind].get_id(name).hex()}")
                    found = True
        return 0 if found else 1
    if cmd == "assign":
        kind = words[1]
        for name in words[2:]:
            uid = uids[kind].get_or_create_id(name)
            print(f"{name}: [{', '.join(str(b) for b in uid)}]")
        return 0
    if cmd == "rename":
        _, kind, old, new = words
        uids[kind].rename(old, new)
        return 0
    if cmd == "fsck":
        return _uid_fsck(tsdb)
    if cmd in uids and len(words) == 2:
        name = words[1]
        try:
            print(f"{cmd} {name}: {uids[cmd].get_id(name).hex()}")
            return 0
        except NoSuchUniqueName:
            print(f"{name}: No such {cmd}")
            return 1
    print(f"unknown uid subcommand: {cmd}", file=sys.stderr)
    return 2


def _uid_fsck(tsdb: TSDB) -> int:
    """Forward/reverse mapping consistency check (UidManager.fsck)."""
    from opentsdb_tpu.uid.uniqueid import ID_FAMILY, MAXID_ROW, NAME_FAMILY

    errors = 0
    fwd: dict[tuple[bytes, bytes], bytes] = {}
    rev: dict[tuple[bytes, bytes], bytes] = {}
    for cells in tsdb.store.scan(tsdb.config.uidtable, b"", b""):
        for c in cells:
            if c.key == MAXID_ROW:
                continue
            if c.family == ID_FAMILY:
                fwd[(c.qualifier, c.key)] = c.value
            elif c.family == NAME_FAMILY:
                rev[(c.qualifier, c.key)] = c.value
    for (kind, name), uid in fwd.items():
        back = rev.get((kind, uid))
        if back != name:
            errors += 1
            print(f"ERROR: forward {kind.decode()} "
                  f"{name.decode('iso-8859-1')} -> {uid.hex()} but "
                  f"reverse says {back!r}")
    for (kind, uid), name in rev.items():
        if (kind, name) not in fwd:
            errors += 1
            print(f"WARN: orphan reverse mapping {kind.decode()} "
                  f"{uid.hex()} -> {name.decode('iso-8859-1')} "
                  "(leaked UID, harmless)")
    print(f"uid fsck: {len(fwd)} forward, {len(rev)} reverse mappings, "
          f"{errors} errors")
    return 1 if errors else 0


def cmd_mkmetric(args) -> int:
    tsdb = make_tsdb(args)
    for name in args.names:
        uid = tsdb.metrics.get_or_create_id(name)
        print(f"metrics {name}: [{', '.join(str(b) for b in uid)}]")
    tsdb.shutdown()
    return 0


def cmd_stats(args) -> int:
    """Print the ``/stats`` lines (or ``--metrics`` Prometheus text)
    from a live server (``--url``) or an opened store — the curl-free
    path for restricted shells and cron probes.

    Store mode opens the WAL like any offline tool (pass --read-only
    against a live writer daemon: stats read fine over the replica
    path and the writer keeps its flock) and reports engine + storage
    stats; server-only counters (connections, RPC latency) need --url.
    """
    if args.url:
        import urllib.request

        url = args.url.rstrip("/") + (
            "/metrics" if args.metrics else "/stats")
        with urllib.request.urlopen(url, timeout=15) as r:
            sys.stdout.write(r.read().decode("utf-8", "replace"))
        return 0
    from opentsdb_tpu.obs.registry import METRICS
    from opentsdb_tpu.stats.collector import StatsCollector

    tsdb = make_tsdb(args)
    c = StatsCollector("tsd")
    tsdb.collect_stats(c)
    METRICS.collect(c)
    if args.metrics:
        sys.stdout.write(METRICS.prometheus_text(extra_lines=c.lines))
    elif c.lines:
        print("\n".join(c.lines))
    tsdb.shutdown()
    return 0


def cmd_sketch_plan(args) -> int:
    """Preview the accuracy-budgeted sketch allocation (sketch/
    budget.py): record densities come from the opened store's raw
    tier (observed fold statistics), the query-workload profile from
    a live daemon's trace ring (--url, the PR-6 slow-query ring) or
    uniform weights. Printing only — the tier applies the budget via
    --sketch-byte-budget at daemon start (a layout change rebuilds)."""
    from opentsdb_tpu.core.const import MAX_TIMESPAN
    from opentsdb_tpu.sketch import budget as _budget

    budget = args.budget
    if budget is None:
        budget = getattr(args, "sketch_byte_budget", None)
    if not budget or budget <= 0:
        print("sketch-plan needs --budget (or --sketch-byte-budget) "
              "> 0", file=sys.stderr)
        return 2
    tsdb = make_tsdb(args)
    try:
        tier = tsdb.rollups
        if tier is not None:
            resolutions = tier.resolutions
            rows = tier._estimate_row_hours()
            hll_p = tier.hll_p
        else:
            cfg = tsdb.config
            resolutions = tuple(sorted(
                int(r) for r in cfg.rollup_resolutions))
            rows = 1
            hll_p = cfg.rollup_hll_p
        records = {r: max(rows // max(r // MAX_TIMESPAN, 1), 1)
                   for r in resolutions}
        workload = None
        if args.url:
            import urllib.request
            try:
                with urllib.request.urlopen(
                        args.url.rstrip("/") + "/api/traces",
                        timeout=10) as resp:
                    ring = json.loads(resp.read())
                workload = _budget.workload_from_ring(ring, resolutions)
                print(f"workload profile from {args.url}: "
                      + ", ".join(
                          f"{r}s={w:g}" for r, w in
                          sorted(workload.items())))
            except Exception as e:
                print(f"could not fetch workload from {args.url}: {e}"
                      f" (using uniform weights)", file=sys.stderr)
        allocs = _budget.allocate(int(budget), records, workload,
                                  hll_p=hll_p)
        print(_budget.render_plan(allocs, int(budget)))
        if tier is not None and tier.sketch_byte_budget:
            current = {r: tuple(a) for r, a in
                       tier.sketch_alloc.items()}
            planned = {r: (a.digest_k, a.moment_k, a.hll_p)
                       for r, a in allocs.items()}
            if current != planned:
                print("NOTE: differs from the tier's current applied "
                      "allocation — restarting the writer with this "
                      "budget will rebuild the tier")
        return 0
    finally:
        tsdb.shutdown()


def cmd_tenants(args) -> int:
    """Per-tenant cardinality report: series counts (exact or HLL
    tier, error declared), the limit governing each tenant, refusal
    counters, and the heavy-hitter summaries — from a live daemon's
    /api/tenants (--url) or an opened store's TENANTS.json-backed
    accountant."""
    if args.url:
        import urllib.request

        with urllib.request.urlopen(
                args.url.rstrip("/") + "/api/tenants", timeout=15) as r:
            info = json.loads(r.read())
        if not info.get("enabled", True):
            print("tenant accounting is off on that daemon "
                  f"(role {info.get('role', '?')})")
            return 0
    else:
        tsdb = make_tsdb(args)
        try:
            if tsdb.tenants is None:
                print("tenant accounting is off (replica store or "
                      "--no-tenant-accounting)", file=sys.stderr)
                return 2
            info = tsdb.tenants.snapshot_info(tsdb.tenant_limits)
        finally:
            tsdb.shutdown()
    if args.json_out:
        json.dump(info, sys.stdout, indent=1)
        print()
        return 0
    print(f"tracked series: {info['tracked_series']}"
          f"  (total ever admitted: {info['total_series']}, "
          f"recovered: {info['recovered_series']})")
    if info.get("mode"):
        print(f"limit mode: {info['mode']}  global limit: "
              f"{info.get('global_limit') or 'unlimited'}")
    hdr = (f"{'tenant':20s} {'series':>10s} {'tier':>6s} "
           f"{'limit':>10s} {'points':>12s} {'refused':>8s} "
           f"{'would':>6s}")
    print(hdr)
    for name, ent in sorted(info["tenants"].items(),
                            key=lambda kv: -kv[1]["series"]):
        err = (f"±{ent['error'] * 100:.0f}%"
               if ent["tier"] == "hll" else "")
        print(f"{name[:20]:20s} {ent['series']:>10d} "
              f"{ent['tier'] + err:>6s} "
              f"{ent.get('limit') or '∞':>10} "
              f"{ent['points']:>12d} {ent['refused']:>8d} "
              f"{ent['would_refuse']:>6d}")
        for hh in ent["top_series"][:args.top]:
            print(f"    series {hh['series']}  points~{hh['points']} "
                  f"(err {hh['err']})")
        for hh in ent["top_prefixes"][:args.top]:
            print(f"    prefix {hh['prefix']}  new-series~"
                  f"{hh['new_series']} (err {hh['err']})")
    return 0


def cmd_version(args) -> int:
    from opentsdb_tpu.build_data import build_data, version_string
    print(version_string(), end="")
    if args.verbose:
        for k, v in build_data().items():
            print(f"{k}: {v}")
    return 0


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tsdb", description="opentsdb_tpu command-line tool")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("tsd", help="start the network daemon")
    common_args(p)
    p.add_argument("--port", type=int, default=4242)
    p.add_argument("--bind", default="0.0.0.0")
    p.add_argument("--staticroot", default=None)
    p.add_argument("--cachedir", default=None)
    p.add_argument("--flush-interval", type=float, default=10.0)
    p.add_argument("--wal-group-ms", type=float, default=0.0,
                   help="WAL group-commit window in ms: concurrent "
                        "durable appends coalesce into one WAL "
                        "write+fsync per window, acks release only "
                        "after the covering fsync (storage/kv.py). "
                        "0 (default) = legacy per-barrier flushing, "
                        "bit-identical WAL bytes")
    p.add_argument("--checkpoint-interval", type=float, default=0.0,
                   help="seconds between sstable spills + WAL truncation "
                        "(0 disables; requires --wal)")
    p.add_argument("--mesh-devices", type=int, default=0,
                   help="shard fused queries over the first N local "
                        "chips (0 = single-device)")
    p.add_argument("--mesh", default="",
                   help="unified mesh execution plane: 'N' = 1-D "
                        "series-hash mesh over N local devices, "
                        "'RxC' = hybrid (host, series) mesh. Eligible "
                        "query reductions + the fused TSST4 stage run "
                        "sharded; supersedes --mesh-devices. On CPU "
                        "set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N "
                        "first (see README 'Mesh execution')")
    p.add_argument("--mesh-plane", default="",
                   help="serving mesh fleet: join the jax.distributed "
                        "plane at HOST:PORT before boot (gloo TCP on "
                        "CPU, native transport on TPU pods) and shard "
                        "the device-resident hot set over this "
                        "process's local devices. Pair with "
                        "--mesh-plane-procs/--mesh-plane-id; fronted "
                        "by a --role router whose fan-out weights each "
                        "backend by its advertised mesh width (see "
                        "README 'Serving mesh')")
    p.add_argument("--mesh-plane-procs", type=int, default=1,
                   help="total process count in the --mesh-plane fleet")
    p.add_argument("--mesh-plane-id", type=int, default=0,
                   help="this process's rank in the --mesh-plane fleet")
    p.add_argument("--devwindow-shards", type=int, default=0,
                   help="shard the device-resident hot window into N "
                        "columns round-robined over the local mesh "
                        "devices (storage/devshard.py): capacity and "
                        "fold throughput scale with device count, and "
                        "the set reshards LIVE on grow/shrink "
                        "(/api/mesh/reshard). 0 = one resident window "
                        "(defaulted to the local device count under "
                        "--mesh-plane)")
    p.add_argument("--device-block-points", type=int, default=None,
                   metavar="N",
                   help="budget of the device block cache of the fused "
                        "plan (compress/devcache.py), in decoded points "
                        "(8 B a point of HBM): what a deployment whose "
                        "history is stored in TSST4 blocks keeps decoded "
                        "on the device beside its window; past it the "
                        "least recently used blocks make room and are "
                        "decoded again when asked for. The daemon holds "
                        "it to half of what the device has left, which "
                        "is also what it is unstated (8,388,608 points "
                        "where the device states no memory); 0 turns "
                        "the cache, and on one device the fused plan, "
                        "off. /stats has what the cache holds as "
                        "tsd.compress.devcache.bytes")
    p.add_argument("--device-window-points", type=int, default=0,
                   metavar="N",
                   help="budget of the device-resident hot window, in "
                        "points summed over metrics (26 B a point of "
                        "HBM, half of it the chunks' padding). Past it "
                        "the chunks holding the oldest data are "
                        "evicted and a request that starts before its "
                        "metric's horizon is served from storage. The "
                        "daemon checks the budget against the device's "
                        "memory at boot and refuses one that does not "
                        "fit. 0 = the default, 67,108,864 (1 << 26); "
                        "/stats has it as tsd.devwindow.points.budget "
                        "and what it holds as tsd.devwindow.bytes")
    p.add_argument("--rollup-device-fold", action="store_true",
                   help="run the rollup checkpoint fold on-device "
                        "behind the mesh plane (f64 accumulation where "
                        "the backend supports it, else a DECLARED f32 "
                        "contract; the applied kind is persisted in "
                        "ROLLUP.json and a kind change rebuilds the "
                        "tier)")
    p.add_argument("--expert-parallel", action="store_true",
                   help="with --mesh: pack mixed /q dashboard batches "
                        "into expert buckets (one mesh dispatch per "
                        "batch; declines declared per-result as "
                        "plan: expert-decline)")
    p.add_argument("--slow-query-ms", type=float, default=0.0,
                   help="trace every /q and log one-line JSON records "
                        "(span tree + plan) for queries at/over this "
                        "wall time; they land in /api/traces too "
                        "(0 disables)")
    p.add_argument("--selfmon-interval", type=float, default=0.0,
                   help="seconds between self-monitoring cycles that "
                        "ingest /stats into the store itself as tsd.* "
                        "series (0 disables)")
    # Distributed serve tier (opentsdb_tpu/serve/).
    p.add_argument("--role", default="writer",
                   choices=["writer", "replica", "router"],
                   help="writer: the single ingesting daemon "
                        "(default). replica: read-only daemon that "
                        "TAILS the writer's WAL continuously with a "
                        "bounded staleness contract (/healthz reports "
                        "lag vs --max-staleness-ms). router: "
                        "storage-free front door fanning /q across "
                        "--backends with deadlines, retries, hedging "
                        "and health-probe ejection")
    p.add_argument("--max-staleness-ms", type=float, default=0.0,
                   help="replica staleness contract: beyond this lag "
                        "every answer is tagged degraded/stale and "
                        "/healthz turns unhealthy (replica role "
                        "defaults to 5000; 0 elsewhere disables)")
    p.add_argument("--tail-interval", type=float, default=0.25,
                   help="seconds between WAL tail cycles (replica)")
    p.add_argument("--backends", default="",
                   help="router: comma-separated replica base URLs "
                        "(http://host:port)")
    p.add_argument("--writer-url", default=None,
                   help="router: forward telnet put lines here")
    p.add_argument("--router-deadline-ms", type=float, default=10000.0)
    p.add_argument("--router-retries", type=int, default=2)
    p.add_argument("--router-hedge-ms", type=float, default=0.0,
                   help="hedge a slow hop after this many ms (0 = "
                        "derive from the observed p95; negative "
                        "disables)")
    p.add_argument("--probe-interval", type=float, default=1.0)
    p.add_argument("--router-eject-after", type=int, default=3)
    # Cluster write tier (opentsdb_tpu/cluster/).
    p.add_argument("--cluster", action="store_true",
                   help="join the cluster write tier: adopt/create "
                        "EPOCH.json next to the WAL, stamp writer "
                        "epochs into WAL segments, fence mutations "
                        "once deposed (writers); accept /promote "
                        "(replicas)")
    p.add_argument("--cluster-owner", default=None,
                   help="this daemon's label in EPOCH.json bumps "
                        "(default host:pid)")
    p.add_argument("--writer-grace-ms", type=float, default=0.0,
                   help="router: promote a replica once the writer's "
                        "/healthz has been dead this long (0 = "
                        "operator-driven failover only)")
    p.add_argument("--writers", default="",
                   help="router: comma-separated writer base URLs; "
                        ">1 enables multi-writer series-hash "
                        "sharding via the ownership map")
    p.add_argument("--cluster-map", default=None,
                   help="router: CLUSTER.json ownership-map path "
                        "(created as an equal split over --writers "
                        "when missing)")
    p.add_argument("--cluster-slots", type=int, default=64,
                   help="hash-space slots for a newly created "
                        "ownership map")
    p.add_argument("--router-rcache", type=int, default=0,
                   help="router: bounded result-cache entries keyed "
                        "by (query, ownership epoch, staleness "
                        "bound); 0 disables")
    p.add_argument("--router-rcache-ms", type=float, default=1000.0,
                   help="router result-cache staleness bound")
    p.add_argument("--trace-sample-n", type=int, default=0,
                   help="trace 1 in N queries into /api/traces even "
                        "when fast — ambient baselines between "
                        "incidents (0 disables)")
    # Tenant cardinality control plane (opentsdb_tpu/tenant/).
    p.add_argument("--tenant-max-series", type=int, default=0,
                   help="refuse a NEW series from any tenant already "
                        "at this many distinct series (declared "
                        "refusal, never a throttle; existing series "
                        "keep ingesting; 0 = unlimited)")
    p.add_argument("--tenant-global-max-series", type=int, default=0,
                   help="directory-wide series cap across every "
                        "tenant (0 = unlimited)")
    p.add_argument("--tenant-limit-mode", default="enforce",
                   choices=["enforce", "warn"],
                   help="warn: count + log would-be refusals "
                        "(tenant.would_refuse) without refusing — "
                        "the dry run before enforcement")
    p.add_argument("--tenant-override", action="append", default=[],
                   metavar="TENANT=LIMIT",
                   help="per-tenant series cap beating "
                        "--tenant-max-series (repeatable; 0 = "
                        "unlimited for that tenant)")
    p.add_argument("--tenant-exact-cutoff", type=int, default=4096,
                   help="distinct series per tenant before its exact "
                        "accounting set folds into an HLL sketch "
                        "(bounded memory under hostile cardinality)")
    p.add_argument("--no-tenant-accounting", action="store_true",
                   help="disable per-tenant series accounting + "
                        "TENANTS.json snapshots entirely")
    # Admission control (any role; all off by default).
    p.add_argument("--query-max-inflight", type=int, default=0,
                   help="load-shedding ladder threshold N: N..2N in "
                        "flight degrades (rollup-only), 2N sheds 503")
    p.add_argument("--query-rate", type=float, default=0.0,
                   help="per-tenant queries/s quota (429 when dry)")
    p.add_argument("--query-burst", type=float, default=8.0,
                   help="per-tenant query bucket burst allowance")
    p.add_argument("--ingest-rate", type=float, default=0.0,
                   help="per-tenant ingest points/s quota")
    p.add_argument("--ingest-queue-points", type=int, default=0,
                   help="global in-flight decoded-point cap; over it "
                        "puts shed with a throttle line")
    p.set_defaults(fn=cmd_tsd)

    p = sub.add_parser("import", help="bulk import text files")
    common_args(p)
    p.add_argument("files", nargs="+")
    p.set_defaults(fn=cmd_import, auto=True)

    p = sub.add_parser("query", help="run a query")
    common_args(p)
    p.add_argument("--graph", metavar="BASEPATH",
                   help="write BASEPATH.png instead of printing ascii")
    p.add_argument("args", nargs="+")
    p.set_defaults(fn=cmd_query)

    p = sub.add_parser("scan", help="dump raw rows")
    common_args(p)
    p.add_argument("--import", dest="importfmt", action="store_true")
    p.add_argument("--delete", action="store_true")
    p.add_argument("args", nargs="+")
    p.set_defaults(fn=cmd_scan)

    p = sub.add_parser("fsck", help="check table consistency")
    common_args(p)
    p.add_argument("--fix", action="store_true")
    p.add_argument("--expect-clean", action="store_true",
                   help="exit 2 if ANY error is found (even with "
                        "--fix) — the crash-harness/CI contract")
    p.set_defaults(fn=cmd_fsck)

    p = sub.add_parser("uid", help="UID administration")
    common_args(p)
    p.add_argument("args", nargs="*")
    p.set_defaults(fn=cmd_uid)

    p = sub.add_parser("mkmetric", help="create metric UIDs")
    common_args(p)
    p.add_argument("names", nargs="+")
    p.set_defaults(fn=cmd_mkmetric)

    p = sub.add_parser(
        "stats", help="print /stats lines from a server or a store")
    common_args(p)
    p.add_argument("--url", default=None,
                   help="base URL of a live tsd (e.g. "
                        "http://localhost:4242): fetch its /stats "
                        "instead of opening a store")
    p.add_argument("--metrics", action="store_true",
                   help="Prometheus text exposition (/metrics) instead "
                        "of classic stats lines")
    p.set_defaults(fn=cmd_stats)

    p = sub.add_parser(
        "sketch-plan",
        help="preview the accuracy-budgeted sketch allocation for a "
             "byte budget (sketch/budget.py)")
    common_args(p)
    p.add_argument("--budget", type=int, default=None,
                   help="summary-byte budget to plan for (falls back "
                        "to --sketch-byte-budget)")
    p.add_argument("--url", default=None,
                   help="base URL of a live tsd: derive the query-"
                        "workload profile from its /api/traces ring "
                        "instead of uniform weights")
    p.set_defaults(fn=cmd_sketch_plan)

    p = sub.add_parser(
        "tenants",
        help="per-tenant series cardinality, limits, refusals and "
             "heavy hitters (opentsdb_tpu/tenant/)")
    common_args(p)
    p.add_argument("--url", default=None,
                   help="base URL of a live tsd: fetch its "
                        "/api/tenants instead of opening a store")
    p.add_argument("--json", dest="json_out", action="store_true",
                   help="raw JSON instead of the table")
    p.add_argument("--top", type=int, default=3,
                   help="heavy-hitter rows to print per tenant")
    p.set_defaults(fn=cmd_tenants)

    p = sub.add_parser("version", help="print build/version information")
    p.add_argument("--verbose", action="store_true")
    p.set_defaults(fn=cmd_version)

    from opentsdb_tpu.tools import ops

    p = sub.add_parser(
        "check", help="Nagios-style threshold probe over /q (check_tsd)")
    ops.add_check_args(p)
    p.set_defaults(fn=ops.cmd_check)

    p = sub.add_parser(
        "drain", help="accept put lines to files during maintenance")
    p.add_argument("--port", type=int, default=4242)
    p.add_argument("--bind", default="0.0.0.0")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("dir", help="directory for per-client drain files")
    p.set_defaults(fn=ops.cmd_drain)

    p = sub.add_parser(
        "clean-cache", help="purge graph cache when the disk is nearly full")
    p.add_argument("--threshold", type=float, default=90.0,
                   help="disk-usage %% that triggers cleaning")
    p.add_argument("--min-age", type=float, default=0.0,
                   help="spare files younger than this many seconds")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("cachedir")
    p.set_defaults(fn=ops.cmd_clean_cache)

    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname)s [%(name)s] %(message)s")
    if getattr(args, "auto", False):
        args.auto_metric = True
    lst = _open_list()
    mark = len(lst)
    try:
        return args.fn(args)
    finally:
        # Commands normally shut their TSDB down themselves; this
        # catches early returns and exceptions (shutdown is
        # idempotent), releasing the WAL flock for embedders/tests
        # that call main() repeatedly in one process. Only this
        # invocation's entries (above the mark) are swept.
        while len(lst) > mark:
            try:
                lst.pop().shutdown()
            except Exception:
                LOG.exception("shutdown during cleanup failed")


if __name__ == "__main__":
    sys.exit(main())
