"""North-star residency benchmark: how many points fit DEVICE-RESIDENT
on ONE chip, and what does a query cost at that scale?

BASELINE.json's north-star metric is "p50 downsample-query latency @ 1B
points". This run loads points straight into the device window (the
serving tier; the storage/WAL path is exercised separately by
bench_scale.py) with a budget sized to the chip's HBM, then answers
REAL executor queries (UID resolution -> plan -> chunked stage ->
apply) against the resident window. The chunked stage
(ops/kernels.window_series_stage_chunks) is what makes this possible:
no concatenated copy of the columns, so the window can approach the
whole HBM instead of half of it.

Writes BENCH_RESIDENT.json. Usage:
    python scripts/bench_resident.py [--points 1000000000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=1_000_000_000)
    ap.add_argument("--series", type=int, default=10_000)
    ap.add_argument("--span", type=int, default=30 * 86400)
    ap.add_argument("--budget", type=int, default=1 << 30,
                    help="devwindow resident budget (points)")
    ap.add_argument("--staging", type=int, default=1 << 22,
                    help="points per upload chunk")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from opentsdb_tpu.utils.jaxenv import setup_compile_cache
    setup_compile_cache()
    dev = jax.devices()[0]
    log(f"device: {dev}")

    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.utils.config import Config

    # Storage stays empty (residency test, not a durability test); the
    # TSDB supplies UID dictionaries + the executor plumbing.
    cfg = Config(auto_create_metrics=True, enable_sketches=False,
                 device_window=True,
                 device_window_staging=args.staging,
                 device_window_points=args.budget)
    tsdb = TSDB(MemKVStore(), cfg, start_compaction_thread=False)

    muid = tsdb.metrics.get_or_create_id("resident.metric")
    hostk = tsdb.tagk.get_or_create_id("host")

    out = {"device": str(dev), "target_points": args.points,
           "series": args.series, "span_s": args.span,
           "budget_points": args.budget}

    base = 1356998400
    pps = max(args.points // args.series, 1)
    step = max(args.span // pps, 1)
    rng = np.random.default_rng(11)
    dw = tsdb.devwindow

    total = 0
    ceiling = None
    t0 = time.perf_counter()
    last = t0
    try:
        for si in range(args.series):
            vuid = tsdb.tagv.get_or_create_id(f"h{si:05d}")
            skey = muid + hostk + vuid
            ts = (base + np.arange(pps, dtype=np.int64) * step
                  + rng.integers(0, max(step - 1, 1)))
            vals = (np.cumsum(rng.normal(0, 1, pps).astype(np.float32))
                    + 100.0)
            dw.append(muid, skey, ts, vals)
            total += pps
            now = time.perf_counter()
            if now - last > 30:
                log(f"  {si + 1}/{args.series} series, {total:,} pts, "
                    f"{total / (now - t0):,.0f} pts/s to device")
                last = now
        dw.flush()
    except Exception as e:  # OOM or upload failure: record the ceiling
        ceiling = f"{type(e).__name__}: {e}"
        log(f"  stopped at {total:,}: {ceiling}")
    load_s = time.perf_counter() - t0

    stats = {}
    try:
        ms = dev.memory_stats()
        stats = {"hbm_bytes_in_use": int(ms.get("bytes_in_use", 0)),
                 "hbm_bytes_limit": int(ms.get("bytes_limit", 0))}
    except Exception:
        pass
    mw = dw._metrics.get(muid)
    out["load"] = {"points": total, "wall_s": round(load_s, 1),
                   "pts_per_s": round(total / max(load_s, 1e-9)),
                   "ceiling": ceiling or "target reached",
                   "resident": dw._total_points,
                   "evicted": dw.evicted_points,
                   "chunks": len(mw.chunks) if mw else 0,
                   "dirty": bool(mw.dirty) if mw else None, **stats}
    log(f"loaded {total:,} pts in {load_s:,.0f}s; resident "
        f"{dw._total_points:,}; evicted {dw.evicted_points:,}; "
        f"hbm {stats.get('hbm_bytes_in_use', 0)/(1<<30):.1f} GiB")

    ex = QueryExecutor(tsdb, backend="tpu")
    start, end = base, base + args.span
    qs = {
        "sum_1havg": QuerySpec("resident.metric", {}, "sum",
                               downsample=(3600, "avg")),
        "rate_sum": QuerySpec("resident.metric", {}, "sum", rate=True,
                              downsample=(3600, "avg")),
        "p95": QuerySpec("resident.metric", {}, "p95",
                         downsample=(3600, "avg")),
    }
    out["queries"] = {}
    for name, spec in qs.items():
        try:
            t1 = time.perf_counter()
            res = ex.run(spec, start, end)
            cold = time.perf_counter() - t1
            times = []
            for _ in range(3):
                t1 = time.perf_counter()
                res = ex.run(spec, start, end)
                times.append(time.perf_counter() - t1)
            out["queries"][name] = {
                "cold_s": round(cold, 3),
                "warm_s": round(float(np.median(times)), 4),
                "groups": len(res),
                "points_out": int(sum(len(r.values) for r in res))}
            log(f"  {name}: cold {cold:.2f} s | warm "
                f"{np.median(times)*1e3:.1f} ms | {len(res)} series out")
        except Exception as e:
            out["queries"][name] = {"error": f"{type(e).__name__}: {e}"}
            log(f"  {name}: FAILED {type(e).__name__}: {e}")

    out["window_hits"] = dw.window_hits
    out["dirty_fallbacks"] = dw.dirty_fallbacks
    with open(os.path.join(REPO, "BENCH_RESIDENT.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({"metric": "resident points on one chip",
                      "value": int(dw._total_points),
                      "unit": "datapoints",
                      "device": str(dev)}))
    tsdb.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
