"""North-star scale run: ingest toward 1B points on this host and
record what the system actually does at that size (VERDICT r02 item 3,
reworked r04 for VERDICT r03 items 2/3/4/6).

Workload shape: TIME-MAJOR — every series advances through time
together, block by block, the way real collectors write (reference
src/core/IncomingDataPoints.java:159-163). This makes devwindow
eviction remove old TIME (not whole early series), so complete_from /
coverage_tail_s mean what they say and the resident-query leg measures
a real range. Synthesis happens OUTSIDE the timed ingest loop (r03's
version synthesized per-chunk inside it).

Measures, and writes to BENCH_SCALE.json (with a clobber guard: a run
smaller than the one already recorded writes only the size-suffixed
artifact, never the canonical file):
- ingest wall time + dps at scale (full system: WAL + sketches +
  devwindow), with a per-subsystem attribution table,
- peak RSS and the host ceiling that set the final size,
- WAL size, checkpoint duration + size, mid-run checkpoints,
- device-window residency/eviction behavior under the budget,
- steady-state resident query latency over the KEPT window,
- cold scan-path latency over 1-day and 1-week ranges (points/s),
- streaming sketch quantile latency over all series.

Run:  python scripts/bench_scale.py [--points 1000000000] [--cpu]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _jax_setup(args):
    """Platform pin, compile cache and the device line, before the
    first JAX use. The native decoder/extension are NOT built here:
    which one a run took is recorded in its artifact (``native_ext`` /
    ``native_decode_built``), decided by whoever ran ``make -C
    native``, never by this script."""
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from opentsdb_tpu.utils.jaxenv import setup_compile_cache
    setup_compile_cache()
    dev = jax.devices()[0]
    log(f"device: {dev}")
    return dev


def rss_gb() -> float:
    with open("/proc/self/status") as f:
        for ln in f:
            if ln.startswith("VmRSS"):
                return int(ln.split()[1]) / (1 << 20)
    return 0.0


def du(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for fn in files:
            try:
                total += os.path.getsize(os.path.join(root, fn))
            except OSError:
                pass
    return total


class Attribution:
    """Per-subsystem wall-time accumulators via bound-method wrapping.

    Timer overhead is two perf_counter calls per wrapped CALL (batch-
    level, not per point) — noise at the chunk sizes used here."""

    def __init__(self) -> None:
        self.acc: dict[str, float] = {}
        self.nested: set[str] = set()

    def wrap(self, obj, name: str, label: str,
             nested_in: str | None = None) -> None:
        """``nested_in`` marks a label whose wall time is already
        contained in another wrapped call (e.g. the WAL write runs
        inside put_many_columnar) — it is reported but excluded from
        the unattributed computation, which would otherwise subtract
        it twice."""
        fn = getattr(obj, name)
        self.acc.setdefault(label, 0.0)
        if nested_in is not None:
            self.nested.add(label)
        acc = self.acc

        def timed(*a, **k):
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                acc[label] += time.perf_counter() - t0

        setattr(obj, name, timed)

    def table(self, wall_s: float) -> dict:
        out = {(f"{k} (nested)" if k in self.nested else k): round(v, 2)
               for k, v in sorted(self.acc.items(), key=lambda x: -x[1])}
        top = sum(v for k, v in self.acc.items() if k not in self.nested)
        out["unattributed"] = round(wall_s - top, 2)
        return out


def write_artifacts(out: dict) -> None:
    """Size-suffixed artifact always (plus a _S<N> suffix for sharded
    runs so a shards=1 control and its shards=N counterpart coexist);
    canonical BENCH_SCALE.json only when this run is at least as large
    as the one it would replace (VERDICT r03 item 4: a 2M smoke run
    silently clobbered the 100M TPU proof)."""
    pts = out["ingest"]["points"]
    # An explicit --shards (1 included) marks a sharding-comparison
    # run: it gets its own _S<N> name so a shards=1 control never
    # clobbers the legacy default-engine artifact for that size. A
    # rollup-enabled run gets _R too — its ingest pays fold costs the
    # plain artifacts must not inherit.
    ssfx = (f"_S{out['shards']}" if out.get("shards") else "")
    if out.get("rollup") is not None:
        ssfx += "_R"
    if out.get("qcache") is not None:
        ssfx += "_Q"
    suffixed = os.path.join(
        REPO, f"BENCH_SCALE_{pts // 1_000_000}M{ssfx}.json")
    with open(suffixed, "w") as f:
        json.dump(out, f, indent=2)
    canonical = os.path.join(REPO, "BENCH_SCALE.json")
    if out.get("rollup") is not None or out.get("qcache") is not None:
        # A rollup run's ingest pays fold costs no plain run pays, and
        # a --repeat-queries run's ingest wall includes the mid-run
        # dirty-set probes; neither may become the canonical
        # cross-round artifact no matter its size.
        log("rollup/qcache run: canonical BENCH_SCALE.json left alone "
            f"(this run in {os.path.basename(suffixed)})")
        return
    prev_pts = -1
    try:
        with open(canonical) as f:
            prev_pts = json.load(f)["ingest"]["points"]
    except Exception:
        pass
    if pts >= prev_pts:
        with open(canonical, "w") as f:
            json.dump(out, f, indent=2)
    else:
        log(f"clobber guard: existing BENCH_SCALE.json records "
            f"{prev_pts:,} points > {pts:,}; canonical left alone "
            f"(this run in {os.path.basename(suffixed)})")


def run_codec_compare(args) -> int:
    """BENCH_COMPRESS.json: the TSST4 acceptance legs. Two identical
    corpora (time-major, mid-run checkpoints) differing ONLY in
    Config.sstable_codec; per leg: ingest dps, on-disk footprint after
    the final checkpoint (+ per-format byte mix and the record-section
    compression ratio), cold 1-week 1h-downsample dashboard, warm
    repeats; on the tsst4 leg additionally the downsample battery
    fused (served plan, decode-plus-aggregate on the blocks) vs
    decode-then-reduce (sstable_fused_agg off -> classic scan), with a
    byte-identical answer check per spec."""
    dev = _jax_setup(args)

    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.storage.sharded import ShardedKVStore
    from opentsdb_tpu.utils.config import Config
    from opentsdb_tpu.utils.gctune import tune_for_ingest
    from opentsdb_tpu.utils.nativeext import ext as native_ext

    shards = max(args.shards, 1)
    base = 1356998400
    pps = max(args.points // args.series, 1)
    step = max(args.span // pps, 1)
    block = min(args.block, pps)
    end = base + pps * step
    ckpt_every = args.checkpoint_every or max(args.points // 20, 1)
    out = {"device": str(dev), "points": args.points,
           "series": args.series, "step_s": step, "shards": shards,
           "checkpoint_every": ckpt_every,
           "native_ext": native_ext is not None,
           "host": {"cores": os.cpu_count(),
                    "ram_gb": round(os.sysconf("SC_PAGE_SIZE")
                                    * os.sysconf("SC_PHYS_PAGES")
                                    / (1 << 30))},
           "fused_battery_extended": bool(args.fused_battery),
           "legs": {}}

    # (label, span, agg, downsample, metric, tag filter, exact):
    # exact rows (TSINT) must match bit-for-bit, float rows to f32
    # tolerance.
    battery = [
        ("1week_1h_sumavg", 7 * 86400, "sum", (3600, "avg"),
         "scale.metric", {}, False),
        ("1week_1h_maxmax", 7 * 86400, "max", (3600, "max"),
         "scale.metric", {}, False),
        ("1week_1h_sumsum", 7 * 86400, "sum", (3600, "sum"),
         "scale.metric", {}, False),
        ("1week_1h_zimsum_count", 7 * 86400, "zimsum", (3600, "count"),
         "scale.metric", {}, False),
        ("1week_1h_p95", 7 * 86400, "p95", (3600, "avg"),
         "scale.metric", {}, False),
        ("1day_1h_sumavg", 86400, "sum", (3600, "avg"),
         "scale.metric", {}, False),
    ]
    if args.fused_battery:
        # Block-stage tag filter / group-by (selector pushdown: non-
        # matching blocks skipped before payload decode) and TSINT
        # rows (exact integer decode on the fused path).
        battery += [
            ("1week_1h_tagfilter_sumavg", 7 * 86400, "sum",
             (3600, "avg"), "scale.metric", {"dc": "d1"}, False),
            ("1week_1h_groupby_sumavg", 7 * 86400, "sum",
             (3600, "avg"), "scale.metric", {"dc": "*"}, False),
            ("1week_1h_int_sumsum", 7 * 86400, "sum", (3600, "sum"),
             "scale.int", {}, True),
            ("1week_1h_int_tagfilter_maxmax", 7 * 86400, "max",
             (3600, "max"), "scale.int", {"dc": "d2"}, True),
        ]

    def build_leg(codec: str) -> dict:
        wd = os.path.join(args.workdir, f"codec-{codec}")
        shutil.rmtree(wd, ignore_errors=True)
        os.makedirs(wd)
        cfg = Config(auto_create_metrics=True, wal_path=wd,
                     shards=shards, sstable_codec=codec,
                     enable_sketches=False, device_window=False)
        store = (ShardedKVStore(wd, shards=shards) if shards > 1
                 else MemKVStore(wal_path=os.path.join(wd, "wal")))
        tsdb = TSDB(store, cfg, start_compaction_thread=False)
        tune_for_ingest()
        rng = np.random.default_rng(7)
        phase = rng.integers(0, max(step - 1, 1), size=args.series)
        if args.fused_battery:
            # A second, low-cardinality tag dimension gives the tag-
            # filter and group-by rows something to push down.
            tags = [{"host": f"h{si:04d}", "dc": f"d{si % 4}"}
                    for si in range(args.series)]
        else:
            tags = [{"host": f"h{si:04d}"} for si in range(args.series)]
        leg: dict = {"codec": codec}
        total = 0
        next_ckpt = ckpt_every
        ckpt_s = 0.0
        t0 = time.perf_counter()
        synth_s = 0.0
        last_log = t0
        for boff in range(0, pps, block):
            bn = min(block, pps - boff)
            ts0 = time.perf_counter()
            rel = (boff + np.arange(bn, dtype=np.int64)) * step
            template = (np.cumsum(
                rng.normal(0, 1, bn).astype(np.float32)) + 100.0)
            blocks = [(base + rel + phase[si],
                       template + np.float32(si))
                      for si in range(args.series)]
            synth_s += time.perf_counter() - ts0
            for si in range(args.series):
                ts, vals = blocks[si]
                total += tsdb.add_batch("scale.metric", ts, vals,
                                        tags[si])
                if args.fused_battery:
                    # Int-valued sibling metric: spills as TSINT
                    # blocks on the tsst4 leg, exact fused decode.
                    iv = (vals * 100).astype(np.int64) + si
                    total += tsdb.add_batch("scale.int", ts, iv,
                                            tags[si])
                if total >= next_ckpt:
                    tc = time.perf_counter()
                    tsdb.checkpoint()
                    ckpt_s += time.perf_counter() - tc
                    next_ckpt = total + ckpt_every
            now = time.perf_counter()
            if now - last_log > 30:
                log(f"  [{codec}] {total:,} pts, "
                    f"{total / (now - t0):,.0f} dps, "
                    f"rss {rss_gb():.1f} GB")
                last_log = now
        tc = time.perf_counter()
        tsdb.checkpoint()
        ckpt_s += time.perf_counter() - tc
        wall = time.perf_counter() - t0
        leg["ingest"] = {
            "points": total, "wall_s": round(wall, 1),
            "dps": round(total / wall),
            "dps_ex_synth": round(total / max(wall - synth_s, 1e-9)),
            "checkpoint_s": round(ckpt_s, 1)}
        leg["dir_bytes"] = du(wd)
        fmt = {f"v{k}": v for k, v in
               sorted(tsdb.store.sstable_format_bytes().items())}
        leg["sstable_bytes_by_format"] = fmt
        raw, enc = tsdb.store.compress_stats()
        leg["compress_ratio"] = (round(raw / enc, 3) if enc else None)
        log(f"  [{codec}] ingest {leg['ingest']}")
        log(f"  [{codec}] dir {leg['dir_bytes'] / (1 << 30):.2f} GB, "
            f"formats {fmt}, ratio {leg['compress_ratio']}")
        return leg, tsdb

    def query_leg(tsdb, leg: dict, codec: str) -> None:
        from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
        ex = QueryExecutor(tsdb, backend="tpu")
        lo, hi = end - 7 * 86400, end
        spec = QuerySpec("scale.metric", {}, "sum",
                         downsample=(3600, "avg"))
        # jit/uid warm on a shifted range, then cold = first pass over
        # the target range through the SERVED plan (fused on tsst4,
        # raw scan on the control), then 3 warm repeats.
        ex.run(spec, lo - 7 * 86400, hi - 7 * 86400)
        t0 = time.perf_counter()
        r_cold, plan, _ = ex.run_with_plan(spec, lo, hi)
        t_cold = time.perf_counter() - t0
        warms = []
        for _ in range(3):
            t0 = time.perf_counter()
            ex.run(spec, lo, hi)
            warms.append(time.perf_counter() - t0)
        leg["cold_1week_scan_s"] = round(t_cold, 4)
        leg["cold_plan"] = plan
        leg["warm_dashboard_s"] = round(sorted(warms)[1], 4)
        leg["warm_all_s"] = [round(w, 4) for w in warms]
        log(f"  [{codec}] cold 1week {t_cold:.3f}s (plan={plan}), "
            f"warm {leg['warm_dashboard_s']:.3f}s")

    # Control leg.
    leg_none, tsdb_none = build_leg("none")
    query_leg(tsdb_none, leg_none, "none")
    out["legs"]["none"] = leg_none
    tsdb_none.shutdown()

    # Compressed leg (+ fused battery).
    leg_c, tsdb_c = build_leg("tsst4")
    query_leg(tsdb_c, leg_c, "tsst4")
    ex = QueryExecutor(tsdb_c, backend="tpu")
    batt = {}
    lo_all = end - 7 * 86400
    from opentsdb_tpu.obs.registry import METRICS
    _dch = METRICS.counter("compress.devcache.hit")
    _dcm = METRICS.counter("compress.devcache.miss")
    _DECL = ("dirty", "int32-span", "grid-too-large",
             "mesh-indivisible", "no-encoded-range", "block-ineligible",
             "mixed-codec", "duplicate-overlap")

    def _declines():
        return {r: METRICS.counter("compress.fused.decline",
                                   {"reason": r}).value for r in _DECL}
    for label, span, agg, ds, metric, tagq, exact in battery:
        spec = QuerySpec(metric, dict(tagq), agg, downsample=ds)
        lo = end - span
        # Warm jit on the shifted window THROUGH the fused plan — a
        # warm-up that lands on another plan leaves the fused program
        # cold and the timed run pays its XLA compile.
        d0 = _declines()
        _, plan_w, _ = ex.run_with_plan(spec, lo - span, end - span)
        d1 = _declines()
        warm_decl = {k: d1[k] - d0[k] for k in d1 if d1[k] != d0[k]}
        if plan_w != "fused":
            # The shifted window can hit blocks the fused path declines
            # (e.g. interleaved mixed-kind tails stored as zlib). Warm
            # the jit on the target window instead, then evict the
            # device cache so the timed run is data-cold but jit-warm —
            # the same treatment the decode-then-reduce control gets
            # (raw warm-up + fragment-cache clear).
            log(f"    warm-up for {label} served plan={plan_w} "
                f"(declines {warm_decl}); warming on target window, "
                f"then evicting every data cache (stage grid, device "
                f"blocks, fragments) so the timed run is data-cold "
                f"with a warm jit")
            _, plan_w, _ = ex.run_with_plan(spec, lo, end)

        def _evict_data_caches():
            ex._fused_stage_cache.clear()
            if ex._devcache is not None:
                ex._devcache.lru.clear()
            ex._frag_cache.clear()

        # Cold trials, median of 3: every trial evicts the data caches
        # (stage grid, device blocks, fragments) and collects garbage
        # OUTSIDE the timer — a single shot is hostage to whichever
        # trial a gen-2 GC pass lands in on a heap that just ingested
        # the whole corpus. Same protocol on both sides.
        _prof = os.environ.get("BENCH_PROFILE_ROW") == label
        t_all = []
        dc_hit = dc_miss = 0
        for _trial in range(3):
            _evict_data_caches()
            gc.collect()
            h0, m0 = _dch.value, _dcm.value
            if _prof and _trial == 0:
                import cProfile
                import pstats
                _pr = cProfile.Profile()
                _pr.enable()
            t0 = time.perf_counter()
            r_f, plan_f, _ = ex.run_with_plan(spec, lo, end)
            t_all.append(time.perf_counter() - t0)
            if _prof and _trial == 0:
                _pr.disable()
                _st = pstats.Stats(_pr).sort_stats("cumulative")
                _st.print_stats(60)
                _st.print_callers("backend_compile")
            if _trial == 0:
                dc_hit, dc_miss = _dch.value - h0, _dcm.value - m0
        t_fused = sorted(t_all)[1]
        t0 = time.perf_counter()
        r_f2 = ex.run(spec, lo, end)
        t_fused_warm = time.perf_counter() - t0
        tsdb_c.config.sstable_fused_agg = False
        ex.run(spec, lo - span, end - span)       # warm raw jit
        tr_all = []
        for _trial in range(3):
            ex._frag_cache.clear()
            gc.collect()
            t0 = time.perf_counter()
            r_r, plan_r, _ = ex.run_with_plan(spec, lo, end)
            tr_all.append(time.perf_counter() - t0)
        t_raw = sorted(tr_all)[1]
        tsdb_c.config.sstable_fused_agg = True
        # Identical bucket grids; TSINT rows bit-for-bit (exact
        # integer decode both sides), float rows to f32 tolerance
        # (the devwindow-plan contract — an alternate exact execution
        # plan may reassociate float32 group sums by an ulp).
        kf = {tuple(sorted(r.tags.items())): r for r in r_f}
        kr = {tuple(sorted(r.tags.items())): r for r in r_r}
        same = (len(r_f) == len(r_r) and set(kf) == set(kr) and all(
            np.array_equal(kf[k].timestamps, kr[k].timestamps)
            and (np.array_equal(kf[k].values, kr[k].values) if exact
                 else np.allclose(kf[k].values, kr[k].values,
                                  rtol=1e-5, atol=1e-5))
            for k in kf))
        batt[label] = {
            "fused_s": round(t_fused, 4),
            "fused_all_s": [round(t, 4) for t in t_all],
            "fused_warm_s": round(t_fused_warm, 4),
            "decode_then_reduce_s": round(t_raw, 4),
            "decode_then_reduce_all_s": [round(t, 4) for t in tr_all],
            "speedup": round(t_raw / max(t_fused, 1e-9), 2),
            "plan_fused": plan_f, "plan_raw": plan_r,
            "plan_warm": plan_w,
            "rows": len(r_f), "exact": bool(exact),
            "devcache_hit": dc_hit, "devcache_miss": dc_miss,
            "answers_match": bool(same)}
        log(f"  fused {label}: {t_fused:.3f}s (plan={plan_f}, "
            f"warm={plan_w}, dev +{dc_hit}h/+{dc_miss}m) vs "
            f"decode-then-reduce {t_raw:.3f}s (x"
            f"{batt[label]['speedup']}, match={same})")
    leg_c["fused_battery"] = batt
    out["legs"]["tsst4"] = leg_c
    tsdb_c.shutdown()

    out["footprint_reduction"] = round(
        leg_none["dir_bytes"] / max(leg_c["dir_bytes"], 1), 3)
    out["cold_scan_ratio_vs_control"] = round(
        leg_c["cold_1week_scan_s"]
        / max(leg_none["cold_1week_scan_s"], 1e-9), 3)
    suffixed = os.path.join(
        REPO, f"BENCH_COMPRESS_{args.points // 1_000_000}M"
              f"_S{shards}.json")
    with open(suffixed, "w") as f:
        json.dump(out, f, indent=2)
    canonical = os.path.join(REPO, "BENCH_COMPRESS.json")
    prev_pts = -1
    try:
        with open(canonical) as f:
            prev_pts = json.load(f)["points"]
    except Exception:
        pass
    if args.points >= prev_pts:
        with open(canonical, "w") as f:
            json.dump(out, f, indent=2)
    else:
        log(f"clobber guard: BENCH_COMPRESS.json records {prev_pts:,} "
            f"points; this run kept in {os.path.basename(suffixed)}")
    log(f"footprint reduction {out['footprint_reduction']}x, cold "
        f"scan ratio {out['cold_scan_ratio_vs_control']}")
    print(json.dumps({"footprint_reduction": out["footprint_reduction"],
                      "cold_scan_ratio":
                          out["cold_scan_ratio_vs_control"]}))
    return 0


def run_ingest_battery(args) -> int:
    """BENCH_INGEST.json: the ingest fast-path acceptance legs.

    One telnet-format corpus (time-major, int/float value mix, two tag
    dimensions) is synthesized ONCE and pushed through the real wire
    path — decode_puts -> ingest_batch with durable acks — by
    concurrent writer threads against a store opened with fsync=True
    (without real fsyncs in the ack path, group commit has nothing to
    coalesce and the comparison would flatter nobody honestly).
    Checkpoints run between rounds so every leg pays its spill + rollup
    fold costs inside the sustained-dps window.

    Legs: the PR-19 ingest shape (scalar per-line decode, no group
    commit, full re-read folds) vs the fast path (vectorized decode,
    group commit, delta folds), each at codec none and tsst4, plus
    single-axis legs isolating group commit and delta folds. A decode
    micro-section times scalar vs vectorized (vs native when built) on
    the same corpus, and every leg's 1h-downsample answer is
    fingerprinted — delta-fold legs must serve byte-identical answers
    to full-refold legs.
    """
    import hashlib

    dev = _jax_setup(args)

    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.obs.registry import METRICS
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
    from opentsdb_tpu.server import wire
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.storage.sharded import ShardedKVStore
    from opentsdb_tpu.utils.config import Config
    from opentsdb_tpu.utils.gctune import tune_for_ingest

    # Untouched defaults mean "size for this host": the acceptance
    # recipe is 100M/4-shard, but a 1-core container gets an honest
    # small corpus with the same shape rather than a number that only
    # measures swap.
    pts = args.points if args.points != 1_000_000_000 else 1_200_000
    series = args.series if args.series != 2_000 else 48
    shards = args.shards or 4
    writers = 4
    base = 1356998400
    step = 2
    pps = max(pts // series, 1)
    end = base + pps * step
    pts = pps * series

    log(f"synthesizing {pts:,} points ({series} series, {pps} "
        f"pts/series, step {step}s, shards {shards})")
    # One stream per writer over DISJOINT series — the collector
    # model: a given series arrives over one connection, concurrency
    # comes from different collectors carrying different hosts.
    # (Interleaving every series into every stream would make writer
    # threads race same-(series,hour) feeds, which soundly kills delta
    # buffers — a hostile shape no real deployment ingests at.) The
    # first time block goes into a separate priming chunk, ingested
    # single-threaded, so UID assignment order (and with it the
    # per-leg answer fingerprint) is deterministic.
    tag_s = [f"host=h{si:03d} dc=d{si % 4}" for si in range(series)]
    prime_lines: list[str] = []
    stream_lines: list[list[str]] = [[] for _ in range(writers)]
    for b in range(pps):
        ts = base + b * step
        for si in range(series):
            if si % 3:
                line = f"put ingest.m {ts} {(b + si) % 1000} {tag_s[si]}"
            else:
                line = (f"put ingest.m {ts} {(b + si) % 1000}."
                        f"{si % 100:02d} {tag_s[si]}")
            (prime_lines if b == 0
             else stream_lines[si % writers]).append(line)
    chunk_lines = 12000
    prime_chunk = ("\n".join(prime_lines) + "\n").encode()
    chunks_by_w = [
        [("\n".join(sl[i:i + chunk_lines]) + "\n").encode()
         for i in range(0, len(sl), chunk_lines)]
        for sl in stream_lines]
    n_lines = pps * series
    all_chunks = [prime_chunk] + [c for cl in chunks_by_w for c in cl]
    del prime_lines, stream_lines

    out = {"device": str(dev), "points": pts, "series": series,
           "step_s": step, "shards": shards, "writers": writers,
           "chunk_lines": chunk_lines,
           "checkpoint_every_points": writers * chunk_lines,
           "fsync": True, "wal_group_ms": 0.5,
           "native_decode_built": wire.native_available(),
           "host": {"cores": os.cpu_count(),
                    "ram_gb": round(os.sysconf("SC_PAGE_SIZE")
                                    * os.sysconf("SC_PHYS_PAGES")
                                    / (1 << 30))},
           "decode": {}, "legs": {}}

    # Decode micro-bench: same corpus, whole pass per decoder. The
    # scalar loop is the PR-19 parse; _decode_python is the vectorized
    # numpy pass; native is the C arena parser when the ext built.
    decoders = [("scalar", lambda ch: wire._decode_scalar(ch)),
                ("vectorized",
                 lambda ch: wire.decode_puts(ch, use_native=False))]
    if wire.native_available():
        decoders.append(
            ("native", lambda ch: wire.decode_puts(ch, use_native=True)))
    for dname, dfn in decoders:
        t0 = time.perf_counter()
        bad = 0
        for ch in all_chunks:
            bad += len(dfn(ch).errors)
        dt = time.perf_counter() - t0
        out["decode"][dname] = {"wall_s": round(dt, 3),
                                "lines_per_s": round(n_lines / dt),
                                "errors": bad}
        log(f"  decode[{dname}]: {n_lines / dt:,.0f} lines/s")
    out["decode"]["vectorized_speedup"] = round(
        out["decode"]["scalar"]["wall_s"]
        / max(out["decode"]["vectorized"]["wall_s"], 1e-9), 2)

    group_counters = ("wal.group.batches", "wal.group.points",
                      "wal.group.fsyncs")
    fold_counters = ("rollup.fold.delta", "rollup.fold.full")

    def run_leg(label: str, codec: str, group: bool, delta: bool,
                scalar_decode: bool, record: bool = True):
        wd = os.path.join(args.workdir, f"ingest-{label}")
        shutil.rmtree(wd, ignore_errors=True)
        os.makedirs(wd)
        cfg = Config(auto_create_metrics=True, wal_path=wd,
                     shards=shards, sstable_codec=codec,
                     enable_sketches=False, device_window=False,
                     enable_rollups=True, rollup_catchup="sync",
                     rollup_delta_fold=delta,
                     wal_group_ms=(0.5 if group else 0.0))
        store = (ShardedKVStore(wd, shards=shards, fsync=True)
                 if shards > 1
                 else MemKVStore(wal_path=os.path.join(wd, "wal"),
                                 fsync=True))
        tsdb = TSDB(store, cfg, start_compaction_thread=False)
        tune_for_ingest()
        c0 = {n: METRICS.counter(n).value
              for n in group_counters + fold_counters}
        w0 = METRICS.timer("wal.group.wait_ms").count
        # Checkpoint after every round of one chunk per writer
        # (~4*chunk_lines points). This approximates the 100M/20-
        # checkpoint recipe's fold regime: what matters for the delta-
        # fold axis is the ratio of corpus re-read per full fold to
        # new points per checkpoint (~10x there, ~12x here), not the
        # absolute corpus size.
        streams = (chunks_by_w if record
                   else [cl[:2] for cl in chunks_by_w])
        n_rounds = max(len(cl) for cl in streams)
        per_r = 1
        written = 0
        ingest_errors: list[str] = []
        lock = threading.Lock()
        ckpt_s = 0.0

        def ingest_one(ch: bytes) -> None:
            nonlocal written
            if scalar_decode:
                batch = wire._decode_scalar(ch)
            else:
                batch = wire.decode_puts(ch)
            n, errs = wire.ingest_batch(tsdb, batch, durable=True)
            with lock:
                written += n
                ingest_errors.extend(errs)
                ingest_errors.extend(batch.errors)

        t0 = time.perf_counter()
        ingest_one(prime_chunk)
        for r in range(n_rounds):
            threads = [
                threading.Thread(target=lambda cl=cl: [
                    ingest_one(ch)
                    for ch in cl[r * per_r:(r + 1) * per_r]])
                for cl in streams]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            tc = time.perf_counter()
            tsdb.checkpoint()
            ckpt_s += time.perf_counter() - tc
        wall = time.perf_counter() - t0
        # Served-answer fingerprint: same corpus every leg, so every
        # leg must produce bit-identical bytes — this is the
        # delta-fold-vs-full-refold parity check on what queries
        # actually serve, not on internals.
        ex = QueryExecutor(tsdb, backend="tpu")
        # Group-by host: per-series rows, so the fingerprint never
        # depends on cross-series float-sum association order.
        spec = QuerySpec("ingest.m", {"host": "*"}, "sum",
                         downsample=(3600, "avg"))
        res, plan, _ = ex.run_with_plan(spec, base - 3600, end + 3600)
        h = hashlib.sha1()
        for row in sorted(res, key=lambda r: tuple(sorted(
                r.tags.items()))):
            h.update(repr(sorted(row.tags.items())).encode())
            h.update(np.ascontiguousarray(row.timestamps).tobytes())
            h.update(np.ascontiguousarray(row.values).tobytes())
        cd = {n: METRICS.counter(n).value - c0[n]
              for n in group_counters + fold_counters}
        leg = {
            "codec": codec, "group_commit": group,
            "delta_folds": delta,
            "decode": "scalar" if scalar_decode else "vectorized",
            "points": written, "wall_s": round(wall, 2),
            "dps": round(written / wall),
            "checkpoint_s": round(ckpt_s, 2),
            "dir_bytes": du(wd),
            "ingest_errors": len(ingest_errors),
            "wal_group": {k.rsplit(".", 1)[1]: cd[k]
                          for k in group_counters},
            "wal_group_waits": METRICS.timer("wal.group.wait_ms").count
                               - w0,
            "folds": {"delta": cd["rollup.fold.delta"],
                      "full": cd["rollup.fold.full"]},
            "query_plan": plan, "answer_sha1": h.hexdigest(),
        }
        tsdb.shutdown()
        if record:
            if written != pts or ingest_errors:
                raise SystemExit(
                    f"leg {label}: wrote {written}/{pts} points, "
                    f"errors {ingest_errors[:3]}")
            out["legs"][label] = leg
            log(f"  [{label}] {leg['dps']:,} dps (ckpt "
                f"{leg['checkpoint_s']}s, folds {leg['folds']}, "
                f"group {leg['wal_group']})")

    # Unrecorded warm-up: first checkpoint + first query pay one-time
    # jit/uid warm costs that would otherwise bias whichever leg runs
    # first (the baseline — inflating the headline speedup).
    run_leg("warmup", "tsst4", True, True, False, record=False)

    legs_def = [
        # PR-19 ingest shape: per-line scalar parse, a barrier (and
        # with fsync=True, an fsync wait) per batch, full re-read folds.
        ("baseline-none", "none", False, False, True),
        ("baseline-tsst4", "tsst4", False, False, True),
        # Single-axis legs (both on the vectorized decoder).
        ("group-tsst4", "tsst4", True, False, False),
        ("delta-tsst4", "tsst4", False, True, False),
        # The full fast path.
        ("fast-none", "none", True, True, False),
        ("fast-tsst4", "tsst4", True, True, False),
    ]
    for label, codec, group, delta, scalar in legs_def:
        run_leg(label, codec, group, delta, scalar)

    fps = {lb: leg["answer_sha1"] for lb, leg in out["legs"].items()}
    speed = (out["legs"]["fast-tsst4"]["dps"]
             / max(out["legs"]["baseline-tsst4"]["dps"], 1))
    out["summary"] = {
        "speedup_fast_vs_baseline_tsst4": round(speed, 2),
        "speedup_fast_vs_baseline_none": round(
            out["legs"]["fast-none"]["dps"]
            / max(out["legs"]["baseline-none"]["dps"], 1), 2),
        # The single-axis legs keep the vectorized decoder, so these
        # are decode+axis gains; the marginal fold-axis gain alone is
        # fast/group, the marginal group-axis gain alone fast/delta.
        "decode_plus_group_gain_tsst4": round(
            out["legs"]["group-tsst4"]["dps"]
            / max(out["legs"]["baseline-tsst4"]["dps"], 1), 2),
        "decode_plus_delta_gain_tsst4": round(
            out["legs"]["delta-tsst4"]["dps"]
            / max(out["legs"]["baseline-tsst4"]["dps"], 1), 2),
        "delta_fold_marginal_gain": round(
            out["legs"]["fast-tsst4"]["dps"]
            / max(out["legs"]["group-tsst4"]["dps"], 1), 2),
        "group_commit_marginal_gain": round(
            out["legs"]["fast-tsst4"]["dps"]
            / max(out["legs"]["delta-tsst4"]["dps"], 1), 2),
        "target_2x_met": bool(speed >= 2.0),
        "answers_identical_across_legs": len(set(fps.values())) == 1,
    }
    if not out["summary"]["answers_identical_across_legs"]:
        log(f"ANSWER MISMATCH across legs: {fps}")

    suffixed = os.path.join(
        REPO, f"BENCH_INGEST_{pts // 1_000}k_S{shards}.json")
    with open(suffixed, "w") as f:
        json.dump(out, f, indent=2)
    canonical = os.path.join(REPO, "BENCH_INGEST.json")
    prev_pts = -1
    try:
        with open(canonical) as f:
            prev_pts = json.load(f)["points"]
    except Exception:
        pass
    if pts >= prev_pts:
        with open(canonical, "w") as f:
            json.dump(out, f, indent=2)
    else:
        log(f"clobber guard: BENCH_INGEST.json records {prev_pts:,} "
            f"points; this run kept in {os.path.basename(suffixed)}")
    log(f"summary: {out['summary']}")
    print(json.dumps(out["summary"]))
    return 0


def run_sketch_serve(args) -> int:
    """BENCH_SKETCH.json: the accuracy-budgeted approximate-serving
    legs. One rollup-backed corpus (digest + moment sketch columns at
    1h and 1d); after the final fold, a pNN dashboard battery runs
    three ways — raw-forced (the exact float64 oracle), digest-served
    (approx=1, t-digest columns), moment-served (same columns, digest
    rung masked so the moment kind answers) — recording wall time,
    the REPORTED error bound, and the ACTUAL |exact - approx| error
    (every answer must sit inside its bound). Plus the tier's
    per-kind sketch bytes (the moment <= 25%-of-digest claim) and the
    Storyboard allocator's plan at three byte budgets over the real
    record densities."""
    dev = _jax_setup(args)

    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
    from opentsdb_tpu.sketch import budget as sbudget
    from opentsdb_tpu.sketch.serving import ApproxSpec
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.storage.sharded import ShardedKVStore
    from opentsdb_tpu.utils.config import Config
    from opentsdb_tpu.utils.gctune import tune_for_ingest
    from opentsdb_tpu.utils.nativeext import ext as native_ext

    shards = max(args.shards, 1)
    base = 1356998400
    pps = max(args.points // args.series, 1)
    step = max(args.span // pps, 1)
    block = min(args.block, pps)
    end = base + pps * step
    ckpt_every = args.checkpoint_every or max(args.points // 20, 1)
    out = {"device": str(dev), "points": args.points,
           "series": args.series, "step_s": step, "shards": shards,
           "checkpoint_every": ckpt_every,
           "native_ext": native_ext is not None,
           "host": {"cores": os.cpu_count(),
                    "ram_gb": round(os.sysconf("SC_PAGE_SIZE")
                                    * os.sysconf("SC_PHYS_PAGES")
                                    / (1 << 30))}}

    wd = os.path.join(args.workdir, "sketch-serve")
    shutil.rmtree(wd, ignore_errors=True)
    os.makedirs(wd)
    cfg = Config(auto_create_metrics=True, wal_path=wd,
                 shards=shards, enable_sketches=False,
                 device_window=False, enable_rollups=True,
                 rollup_catchup="sync",
                 rollup_sketch_min_res=3600)  # digests at 1h too
    store = (ShardedKVStore(wd, shards=shards) if shards > 1
             else MemKVStore(wal_path=os.path.join(wd, "wal")))
    tsdb = TSDB(store, cfg, start_compaction_thread=False)
    tune_for_ingest()
    rng = np.random.default_rng(7)
    phase = rng.integers(0, max(step - 1, 1), size=args.series)
    tags = [{"host": f"h{si:04d}"} for si in range(args.series)]
    total = 0
    next_ckpt = ckpt_every
    ckpt_s = synth_s = 0.0
    t0 = time.perf_counter()
    last_log = t0
    for boff in range(0, pps, block):
        bn = min(block, pps - boff)
        ts0 = time.perf_counter()
        rel = (boff + np.arange(bn, dtype=np.int64)) * step
        # Lognormal-ish positive values: the moment solver's log
        # domain and the digests both get realistic latency shapes.
        template = np.exp(
            rng.normal(0, 0.6, bn).astype(np.float32)) * 100.0
        blocks = [(base + rel + phase[si],
                   template * np.float32(1.0 + si / args.series))
                  for si in range(args.series)]
        synth_s += time.perf_counter() - ts0
        for si in range(args.series):
            ts, vals = blocks[si]
            total += tsdb.add_batch("scale.metric", ts, vals,
                                    tags[si])
            if total >= next_ckpt:
                tc = time.perf_counter()
                tsdb.checkpoint()
                ckpt_s += time.perf_counter() - tc
                next_ckpt = total + ckpt_every
        now = time.perf_counter()
        if now - last_log > 30:
            log(f"  {total:,} pts, {total / (now - t0):,.0f} dps, "
                f"rss {rss_gb():.1f} GB")
            last_log = now
    tc = time.perf_counter()
    tsdb.checkpoint()
    ckpt_s += time.perf_counter() - tc
    wall = time.perf_counter() - t0
    out["ingest"] = {"points": total, "wall_s": round(wall, 1),
                     "dps": round(total / wall),
                     "dps_ex_synth": round(
                         total / max(wall - synth_s, 1e-9)),
                     "checkpoint_s": round(ckpt_s, 1)}
    log(f"ingest {out['ingest']}")
    tier = tsdb.rollups
    assert tier is not None and tier.ready
    sk_bytes = dict(tier.sketch_bytes)
    per_res = {str(r): dict(k) for r, k in
               sorted(tier.sketch_bytes_res.items())}
    # The size claim is about EQUIVALENT columns: at the coarsest
    # resolution the windows are dense enough that the t-digest
    # saturates its k centroids — that's the column a moment sketch
    # replaces byte-for-byte. (At sparse fine windows a digest
    # degenerates to per-point centroids and is smaller than any
    # fixed-size summary; both numbers are recorded.)
    coarse = str(max(tier.resolutions))
    cres = per_res.get(coarse, {})
    ratio = (cres.get("moment", 0) / max(cres.get("tdigest", 1), 1))
    out["tier"] = {
        "records_written": tier.records_written,
        "sketch_bytes": sk_bytes,
        "sketch_bytes_by_res": per_res,
        "moment_vs_tdigest_ratio_coarse": round(ratio, 4),
        "dir_bytes": du(wd),
        "sketch_alloc": {str(r): list(a) for r, a in
                         sorted(tier.sketch_alloc.items())},
    }
    log(f"tier: {out['tier']['records_written']:,} records, "
        f"sketch bytes {sk_bytes}; at {coarse}s "
        f"moment/tdigest = {ratio:.3f}")

    # Storyboard allocator at three budgets over the REAL densities.
    rows = tier._estimate_row_hours()
    records = {r: max(rows // max(r // 3600, 1), 1)
               for r in tier.resolutions}
    full_cost = sum(
        sbudget.record_bytes(128, 8, tier.hll_p) * n
        for n in records.values())
    out["budgets"] = []
    for frac in (0.05, 0.25, 1.0):
        budget = int(full_cost * frac)
        allocs = sbudget.allocate(budget, records, hll_p=tier.hll_p)
        out["budgets"].append({
            "budget_bytes": budget,
            "planned_bytes": sum(a.total_bytes
                                 for a in allocs.values()),
            "alloc": {str(r): {"digest_k": a.digest_k,
                               "moment_k": a.moment_k,
                               "bytes_per_record": a.bytes_per_record}
                      for r, a in sorted(allocs.items())}})
        log(f"budget {budget / (1 << 20):,.0f} MB -> "
            f"{out['budgets'][-1]['alloc']}")

    # The pNN dashboard battery, three serving modes each.
    ex = QueryExecutor(tsdb, backend="cpu")

    def aligned(span: int, interval: int) -> tuple[int, int]:
        """Window-aligned [lo, hi] ending at the corpus tail — the
        dashboard shape (grafana-style panels align their ranges),
        and what lets the approx rail cache serve repeats."""
        e = end // interval * interval
        return e - span, e - 1

    battery = [
        ("1week_1h_p95", *aligned(7 * 86400, 3600), "max",
         (3600, "p95")),
        ("1week_1h_p99", *aligned(7 * 86400, 3600), "avg",
         (3600, "p99")),
        ("1month_1d_p99", *aligned(30 * 86400, 86400), "max",
         (86400, "p99")),
        ("1week_2h_p50_hostgroup", *aligned(7 * 86400, 7200), "max",
         (7200, "p50")),
    ]
    out["queries"] = []
    for label, lo, hi, gagg, ds in battery:
        tags_q = ({"host": "h0000|h0001|h0002|h0003"}
                  if label.endswith("hostgroup") else {})
        spec = QuerySpec("scale.metric", tags_q, gagg, downsample=ds)
        rec = {"label": label, "m": f"{gagg}:{ds[0]}s-{ds[1]}"}

        def timed(fn, n=3):
            walls = []
            res = None
            for _ in range(n):
                tq = time.perf_counter()
                res = fn()
                walls.append(time.perf_counter() - tq)
            return res, walls

        # Raw-forced (exact): cold first, then warm repeats through
        # the fragment cache — the sketch legs must beat the WARM
        # number for the speedup to mean anything.
        tq = time.perf_counter()
        exact = ex.run(spec, lo, hi)
        rec["raw_cold_s"] = round(time.perf_counter() - tq, 4)
        exact, walls = timed(lambda: ex.run(spec, lo, hi))
        rec["raw_warm_s"] = round(min(walls), 4)

        def approx_leg(kind_label):
            got, walls = timed(lambda: ex.run_approx(
                spec, lo, hi, approx=ApproxSpec(True, None)))
            rs, plan, _c, info = got
            leg = {"wall_s": round(min(walls), 4), "plan": plan}
            if info is None:
                leg["served"] = False
                return leg
            leg.update(served=True, kind=info.kind,
                       reported_error=info.error,
                       reported_rel_error=round(info.rel_error, 6))
            ek = {tuple(sorted(r.tags.items())): r for r in exact}
            worst = 0.0
            n_buckets = 0
            for r in rs:
                ref = ek.get(tuple(sorted(r.tags.items())))
                if ref is None:
                    continue
                ev = dict(zip(ref.timestamps.tolist(),
                              ref.values.tolist()))
                for t, v in zip(r.timestamps.tolist(),
                                r.values.tolist()):
                    if t in ev:
                        worst = max(worst, abs(ev[t] - v))
                        n_buckets += 1
            leg["actual_error"] = round(worst, 6)
            leg["buckets_checked"] = n_buckets
            leg["within_bounds"] = bool(worst <= info.error + 1e-9)
            return leg

        rec["digest"] = approx_leg("tdigest")
        # Moment leg: mask the digest rung so the SAME cells serve
        # through the moment column (kind selection is per-res).
        saved = dict(tier.sketch_alloc)
        tier.sketch_alloc = {r: (0, a[1], 0)
                             for r, a in saved.items()}
        try:
            rec["moment"] = approx_leg("moment")
        finally:
            tier.sketch_alloc = saved
        for leg_name in ("digest", "moment"):
            leg = rec[leg_name]
            if leg.get("served"):
                leg["speedup_vs_raw_warm"] = round(
                    rec["raw_warm_s"] / max(leg["wall_s"], 1e-9), 1)
                leg["speedup_vs_raw_cold"] = round(
                    rec["raw_cold_s"] / max(leg["wall_s"], 1e-9), 1)
        out["queries"].append(rec)
        log(f"  {label}: raw {rec['raw_cold_s']}s cold / "
            f"{rec['raw_warm_s']}s warm; digest "
            f"{rec['digest'].get('wall_s')}s "
            f"({rec['digest'].get('speedup_vs_raw_warm')}x, "
            f"in-bounds={rec['digest'].get('within_bounds')}); "
            f"moment {rec['moment'].get('wall_s')}s "
            f"({rec['moment'].get('speedup_vs_raw_warm')}x, "
            f"in-bounds={rec['moment'].get('within_bounds')})")

    served = [q for q in out["queries"]
              if q["digest"].get("served")]
    out["summary"] = {
        "min_digest_speedup_vs_raw_warm": min(
            (q["digest"]["speedup_vs_raw_warm"] for q in served),
            default=None),
        "all_within_bounds": all(
            q[leg].get("within_bounds", True)
            for q in out["queries"] for leg in ("digest", "moment")
            if q[leg].get("served")),
        "moment_vs_tdigest_bytes_coarse": round(ratio, 4),
    }
    tsdb.shutdown()
    suffixed = os.path.join(
        REPO, f"BENCH_SKETCH_{total // 1_000_000}M_S{shards}.json")
    for path in (suffixed, os.path.join(REPO, "BENCH_SKETCH.json")):
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
    log(f"summary: {out['summary']} -> BENCH_SKETCH.json")
    return 0


def _synth_mesh_corpus(n_series: int, pps: int, step: int):
    """The mesh-bench corpus as a pure function of (n_series, pps,
    step): one sequential rng stream, so every fleet process can
    re-derive the SAME corpus independently and take its series
    partition by index.  Returns (series, rng) — the rng is handed on
    so the integer corpus continues the identical stream."""
    rng = np.random.default_rng(7)
    series = []
    for _si in range(n_series):
        ts = (np.arange(pps, dtype=np.int64) * step
              + int(rng.integers(0, max(step - 1, 1))))
        vals = np.cumsum(rng.normal(0, 1, pps)) + 50.0
        series.append((ts, vals))
    return series, rng


def _synth_int_corpus(rng, n_series: int, B: int, interval: int):
    """Dense integer-valued series (every contribution exact in f64,
    so any shard/process topology must reproduce the sum bit-for-bit).
    Continues the corpus rng stream."""
    out = []
    for si in range(n_series):
        its = (np.arange(B, dtype=np.int64) * interval
               + (si * 7) % interval)
        out.append((its, rng.integers(-500, 500, B).astype(np.float64)))
    return out


def _fleet_child() -> int:
    """One process of the multi-process BENCH_MESH leg: join the gloo
    plane, re-derive the corpus, keep the series whose index hashes to
    this process (si % nproc — the same series-axis ownership rule the
    serving fleet uses), run the mergeable dashboard kernels on the
    LOCAL device mesh, and write grids + walls for the parent to merge.
    Timing sections are barrier-aligned across the fleet so every
    process times the same kernel concurrently."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    e = os.environ
    pid = int(e["MESHBENCH_PROC_ID"])
    nproc = int(e["MESHBENCH_NPROC"])
    outdir = e["MESHBENCH_OUT"]
    n_series = int(e["MESHBENCH_SERIES"])
    pps = int(e["MESHBENCH_PPS"])
    step = int(e["MESHBENCH_STEP"])
    interval = int(e["MESHBENCH_INTERVAL"])
    B = int(e["MESHBENCH_BUCKETS"])
    sample_n = int(e["MESHBENCH_FOLD_SAMPLE"])
    from opentsdb_tpu.parallel import fleet
    fleet.init_plane(e["MESHBENCH_COORD"], nproc, pid)
    from jax.experimental import multihost_utils

    from opentsdb_tpu.parallel.compile import set_mesh_devices
    from opentsdb_tpu.parallel.mesh import make_mesh
    from opentsdb_tpu.parallel.sharded import (pack_shards,
                                               sharded_downsample_group)
    from opentsdb_tpu.rollup import summary
    local = jax.local_devices()
    D = len(local)
    set_mesh_devices(D)
    mesh = make_mesh(D, devices=np.array(local))
    series, rng = _synth_mesh_corpus(n_series, pps, step)
    int_series = _synth_int_corpus(rng, min(n_series, 256), B, interval)
    mine = series[pid::nproc]
    int_mine = int_series[pid::nproc]
    sample_mine = [series[si] for si in range(sample_n)
                   if si % nproc == pid]
    del series, int_series

    def timed(fn, repeats=3):
        fn()                        # warm (compile)
        best = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = fn()
            best.append(time.perf_counter() - t0)
        return r, min(best)

    def leg(part, agg_down, agg_group):
        ts_d, vals_d, sid_d, valid_d, sps = part

        def run():
            gv, gm = sharded_downsample_group(
                ts_d, vals_d, sid_d, valid_d, mesh=mesh,
                series_per_shard=sps, num_buckets=B,
                interval=interval, agg_down=agg_down,
                agg_group=agg_group)
            return np.asarray(gv), np.asarray(gm)
        return run

    arrays, walls = {}, {}
    packed = pack_shards(mine, D)
    for agg_down, agg_group, label in (("avg", "sum", "sum-of-avg"),
                                       ("sum", "max", "max-of-sum")):
        multihost_utils.sync_global_devices("fleet-" + label)
        (gv, gm), w = timed(leg(packed, agg_down, agg_group))
        arrays["gv_" + label] = gv
        arrays["gm_" + label] = gm
        walls[label] = w
    int_packed = pack_shards(int_mine, D)
    multihost_utils.sync_global_devices("fleet-int")
    (gv, gm), w = timed(leg(int_packed, "sum", "sum"))
    arrays["gv_int"] = gv
    arrays["gm_int"] = gm
    walls["count-sum-integer"] = w
    # Fold contract material (byte-compared by the parent, untimed —
    # the timed fold battery is the single-process leg's).
    folds = summary.window_summaries_sharded(sample_mine, 3600, mesh)
    for k, (wb, rec) in enumerate(folds):
        arrays[f"fold_wb_{k}"] = np.asarray(wb)
        arrays[f"fold_rec_{k}"] = np.frombuffer(rec.tobytes(), np.uint8)
    np.savez(os.path.join(outdir, f"proc{pid}.npz"), **arrays)
    with open(os.path.join(outdir, f"proc{pid}.json"), "w") as f:
        json.dump({"walls": walls, "devices_local": D,
                   "series_local": len(mine)}, f)
    return 0


def _reshard_under_ingest(n_shards_start=8, targets=(12, 4)) -> dict:
    """Live grow/shrink reshard of the sharded resident hot set while
    ingest keeps landing, polled through the real query path.  The
    polled range is frozen BEFORE the reshard and all concurrent
    ingest appends strictly later timestamps, so every polled answer
    must be byte-identical to the baseline (served resident from the
    pre- or post-swap set) or a declared decline to the scan path —
    which reads the same storage and must ALSO match.  Any deviation
    is a wrong answer (a half-redistributed hot set)."""
    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.utils.config import Config
    BT = 1356998400
    SPAN = 7200
    t = TSDB(MemKVStore(),
             Config(auto_create_metrics=True, enable_sketches=False,
                    device_window=True, devwindow_shards=n_shards_start),
             start_compaction_thread=False)
    rng = np.random.default_rng(5)
    n_series, n_pts = 64, 4800
    for i in range(n_series):
        ts = BT + np.sort(rng.choice(SPAN, n_pts, replace=False))
        t.add_batch("mesh.bench.cpu", ts, rng.normal(100, 10, n_pts),
                    {"host": f"h{i}"})
    dw = t.devwindow
    dw.flush()
    ex = QueryExecutor(t, backend="tpu")
    spec = QuerySpec("mesh.bench.cpu", {}, "sum",
                     downsample=(600, "count"))

    def grids():
        got = ex.run(spec, BT, BT + SPAN)
        return [(r.timestamps.tobytes(), r.values.tobytes())
                for r in got]

    base = grids()
    polls = hits = declines = wrong = 0
    wrote = [0]
    k_ing = [0]
    steps = []
    ing = np.random.default_rng(99)

    ingest_lock = threading.Lock()

    def ingest_once():
        # Live ingest, strictly later than the polled range (+60:
        # query ranges are end-INCLUSIVE, so the polled range owns
        # BT+SPAN itself) — journaled dual-writes while the rebuild
        # is off-gate.
        with ingest_lock:
            ts = (BT + SPAN + 60 + wrote[0] * 60
                  + np.arange(20, dtype=np.int64) * 60)
            t.add_batch("mesh.bench.cpu", ts, ing.normal(5, 1, 20),
                        {"host": f"h{k_ing[0] % n_series}"})
            wrote[0] += 20
            k_ing[0] += 1

    poll_lock = threading.Lock()

    def poll_once():
        nonlocal polls, hits, declines, wrong
        with poll_lock:            # mid-rebuild probe runs in the
            h0 = dw.window_hits    # reshard thread, the loop in main
            got = grids()
            polls += 1
            if dw.window_hits > h0:
                hits += 1
            else:
                declines += 1
            if got != base:
                wrong += 1

    # The reshard can finish faster than one concurrent poll round,
    # so a _split_series hook injects one GUARANTEED probe while the
    # journal is armed and the new shard set is mid-build.
    from opentsdb_tpu.storage.devshard import ShardedDeviceWindow
    orig_split = ShardedDeviceWindow._split_series
    mid = [0]

    def mid_build_probe(metric_snaps):
        ingest_once()
        poll_once()
        mid[0] += 1
        return orig_split(metric_snaps)

    ShardedDeviceWindow._split_series = staticmethod(mid_build_probe)
    try:
        for target in targets:
            done = []
            rt = threading.Thread(
                target=lambda: done.append(
                    dw.reshard(n_shards=target)))
            during = polls
            rt.start()
            while rt.is_alive():
                ingest_once()
                poll_once()
            rt.join()
            assert done and done[0]["n_shards"] == target
            steps.append({"to_shards": target,
                          "reshard_ms": done[0]["reshard_ms"],
                          "polls_during": polls - during})
            poll_once()            # post-swap answer still exact
    finally:
        ShardedDeviceWindow._split_series = orig_split
    assert mid[0] == len(targets), "mid-rebuild probe never fired"
    # Appends that landed around the swaps route by the new mapping
    # and serve resident over the extended range.
    dw.flush()
    hi = BT + SPAN + 60 + wrote[0] * 60
    h0 = dw.window_hits
    tail = ex.run(spec, BT + SPAN + 60, hi)
    tail_resident = dw.window_hits > h0
    tail_pts = float(sum(np.asarray(r.values).sum() for r in tail))
    t.shutdown()
    assert wrong == 0, f"{wrong}/{polls} polled answers diverged"
    assert tail_pts == float(wrote[0]), (tail_pts, wrote[0])
    return {"resident_series": n_series,
            "resident_points": n_series * n_pts,
            "shards_path": [n_shards_start, *targets],
            "steps": steps, "polls": polls,
            "mid_rebuild_polls": mid[0], "resident_hits": hits,
            "declared_declines": declines, "wrong_answers": wrong,
            "ingested_during": wrote[0],
            "ingested_served_resident_after": bool(tail_resident)}


def run_mesh_fleet_bench(args) -> int:
    """The BENCH_MESH *multi-process* leg: N gloo processes form one
    plane (parallel/fleet.init_plane — the served deployment mode's
    bootstrap), each owns the series whose index hashes to it, runs
    the mergeable dashboard kernels over its LOCAL device mesh, and
    the parent merges the per-process group grids exactly the way
    serve/router.py merges fan-out answers (sum→add, max→max,
    mask→or).  The merged fleet answer is checked against a 1-device
    control over the full corpus under the declared per-kernel
    contract:

      integer-sum + fold kernels  -> byte-identical
      stage kernels (f32 sum/avg) -> rel diff < 1e-4

    Wall-clock: fleet wall per kernel = max over processes (they run
    barrier-aligned), vs the 1-device control timed alone afterwards.
    Then the live grow/shrink reshard-under-ingest probe runs on a
    sharded resident hot set (zero wrong answers tolerated).  Results
    merge into BENCH_MESH.json under "multiprocess" (clobber-guarded
    like the main leg)."""
    import re
    import socket
    import tempfile
    nproc = int(args.fleet)
    shape = args.mesh.strip().lower()
    if "x" in shape:
        r_s, _, c_s = shape.partition("x")
        want_devs = int(r_s) * int(c_s)
    else:
        want_devs = int(shape)
    if want_devs % nproc:
        log(f"fleet {nproc} does not divide mesh {shape}")
        return 1
    dpp = want_devs // nproc
    # A gloo/CPU leg: the N children are told apart by a CPU flag
    # (--xla_force_host_platform_device_count) and pin the CPU
    # platform; N processes cannot share one host's chips, and the
    # control below must run where the children ran.
    if not args.cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        log("--fleet is a gloo/CPU leg (N processes cannot share this "
            "host's chips): pass --cpu")
        return 2
    import jax
    jax.config.update("jax_platforms", "cpu")
    from opentsdb_tpu.parallel.compile import set_mesh_devices
    from opentsdb_tpu.parallel.mesh import make_mesh
    from opentsdb_tpu.parallel.sharded import (pack_shards,
                                               sharded_downsample_group)
    from opentsdb_tpu.rollup import summary

    base_pps = max(args.points // args.series, 1)
    step = max(args.span // base_pps, 1)
    interval = 3600
    B = args.span // interval
    sample_n = min(64, args.series)
    total_points = args.series * base_pps

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    outdir = tempfile.mkdtemp(prefix="meshfleet_")
    env_base = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env_base.get("XLA_FLAGS", ""))
    env_base["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={dpp}").strip()
    env_base.update({
        "MESHBENCH_COORD": f"127.0.0.1:{port}",
        "MESHBENCH_NPROC": str(nproc),
        "MESHBENCH_OUT": outdir,
        "MESHBENCH_SERIES": str(args.series),
        "MESHBENCH_PPS": str(base_pps),
        "MESHBENCH_STEP": str(step),
        "MESHBENCH_INTERVAL": str(interval),
        "MESHBENCH_BUCKETS": str(B),
        "MESHBENCH_FOLD_SAMPLE": str(sample_n),
    })
    log(f"fleet: {nproc} processes x {dpp} devices "
        f"(width {want_devs}), {total_points:,} points...")
    procs = []
    for pid in range(nproc):
        env = dict(env_base)
        env["MESHBENCH_PROC_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    rc = 0
    for pid, p in enumerate(procs):
        try:
            _out, err = p.communicate(timeout=3000)
        except subprocess.TimeoutExpired:
            p.kill()
            _out, err = p.communicate()
            rc = 1
            log(f"fleet proc {pid}: TIMEOUT")
            continue
        if p.returncode != 0:
            rc = 1
            log(f"fleet proc {pid} rc={p.returncode}\n{err[-3000:]}")
    if rc:
        return rc
    children = []
    for pid in range(nproc):
        with open(os.path.join(outdir, f"proc{pid}.json")) as f:
            meta = json.load(f)
        children.append(
            (meta, np.load(os.path.join(outdir, f"proc{pid}.npz"))))

    # Control: the SAME corpus on one device, timed alone (the fleet
    # timed itself first so the two legs never contend).
    one = make_mesh(1, devices=np.array(jax.devices()[:1]))
    set_mesh_devices(1)
    log("fleet control (1-device mesh, full corpus)...")
    series, rng = _synth_mesh_corpus(args.series, base_pps, step)
    int_series = _synth_int_corpus(rng, min(args.series, 256), B,
                                   interval)

    def timed(fn, repeats=3):
        fn()
        best = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = fn()
            best.append(time.perf_counter() - t0)
        return r, min(best)

    def ctrl(part, agg_down, agg_group):
        ts_1, vals_1, sid_1, valid_1, sps1 = part

        def run():
            gv, gm = sharded_downsample_group(
                ts_1, vals_1, sid_1, valid_1, mesh=one,
                series_per_shard=sps1, num_buckets=B,
                interval=interval, agg_down=agg_down,
                agg_group=agg_group)
            return np.asarray(gv), np.asarray(gm)
        return run

    packed1 = pack_shards(series, 1)
    int_packed1 = pack_shards(int_series, 1)
    ctrl_grids, ctrl_walls = {}, {}
    for agg_down, agg_group, label in (("avg", "sum", "sum-of-avg"),
                                       ("sum", "max", "max-of-sum")):
        (gv, gm), w = timed(ctrl(packed1, agg_down, agg_group))
        ctrl_grids[label] = (gv, gm)
        ctrl_walls[label] = w
    (gv, gm), w = timed(ctrl(int_packed1, "sum", "sum"))
    ctrl_grids["count-sum-integer"] = (gv, gm)
    ctrl_walls["count-sum-integer"] = w
    fold_ctrl = summary.window_summaries_sharded(series[:sample_n],
                                                 3600, one)
    del packed1, int_packed1

    # Merge the per-process grids the router way and hold the contract.
    def merge(label, key, combine, fill):
        gms = [np.asarray(ch[f"gm_{key}"]) for _m, ch in children]
        gvs = [np.where(m, np.asarray(ch[f"gv_{key}"]), fill)
               for m, (_m2, ch) in zip(gms, children)]
        gm = gms[0]
        gv = gvs[0]
        for m, v in zip(gms[1:], gvs[1:]):
            gv = combine(gv, v)
            gm = gm | m
        gv_c, gm_c = ctrl_grids[label]
        assert (gm == gm_c).all(), f"{label}: fleet mask != control"
        rel = float((np.abs(gv[gm] - gv_c[gm_c])
                     / np.maximum(np.abs(gv_c[gm_c]), 1.0)).max()) \
            if gm_c.any() else 0.0
        byte = gv[gm].tobytes() == gv_c[gm_c].tobytes()
        return rel, byte

    rel_sum, _ = merge("sum-of-avg", "sum-of-avg", np.add, 0.0)
    rel_max, byte_max = merge("max-of-sum", "max-of-sum", np.maximum,
                              -np.inf)
    rel_int, byte_int = merge("count-sum-integer", "int", np.add, 0.0)
    assert rel_sum < 1e-4 and rel_max < 1e-4, (rel_sum, rel_max)
    assert byte_int, "integer sum not byte-identical across the fleet"

    fold_byte = True
    for si in range(sample_n):
        owner, k = si % nproc, si // nproc
        ch = children[owner][1]
        wb_c, rec_c = fold_ctrl[si]
        fold_byte &= bool(
            np.array_equal(np.asarray(wb_c), ch[f"fold_wb_{k}"])
            and rec_c.tobytes() == ch[f"fold_rec_{k}"].tobytes())
    assert fold_byte, "fleet fold not byte-identical vs control"

    dashboard = {}
    fleet_total = ctrl_total = 0.0
    for label in ("sum-of-avg", "max-of-sum", "count-sum-integer"):
        fw = max(m["walls"][label] for m, _ch in children)
        cw = ctrl_walls[label]
        fleet_total += fw
        ctrl_total += cw
        dashboard[label] = {
            "fleet_s": round(fw, 4),
            "per_process_s": [round(m["walls"][label], 4)
                              for m, _ch in children],
            "single_device_s": round(cw, 4),
            "speedup": round(cw / max(fw, 1e-9), 2)}
    overall = ctrl_total / max(fleet_total, 1e-9)
    cores = len(os.sched_getaffinity(0)) if hasattr(
        os, "sched_getaffinity") else os.cpu_count()

    log("fleet reshard-under-ingest probe...")
    reshard = _reshard_under_ingest()

    mp = {"processes": nproc, "devices_per_process": dpp,
          "width": want_devs, "corpus_points": int(total_points),
          "series": args.series, "span_s": args.span,
          "host": {"cores": cores},
          "dashboard": dashboard,
          "dashboard_speedup_overall": round(overall, 2),
          "meets_4x_target": bool(overall >= 4.0),
          "contract": {
              "declared": {"integer-sum": "byte-identical",
                           "fold": "byte-identical",
                           "stage(f32 sum/avg/max)": "rel<1e-4"},
              "integer_sum_byte_identical": bool(byte_int),
              "fold_sample_series": sample_n,
              "fold_byte_identical": bool(fold_byte),
              "max_of_sum_byte_identical": bool(byte_max),
              "stage_max_rel_diff": max(rel_sum, rel_max)},
          "reshard_under_ingest": reshard}
    if cores < want_devs:
        mp["note"] = (f"host grants {cores} core(s) < mesh width "
                      f"{want_devs}: wall-clock scaling is core-bound "
                      f"here; contract + reshard checks are "
                      f"host-independent")
    for m, ch in children:
        ch.close()
    shutil.rmtree(outdir, ignore_errors=True)

    suffixed = os.path.join(
        REPO, f"BENCH_MESH_{total_points // 1_000_000}M_{shape}.json")
    for path in (suffixed, os.path.join(REPO, "BENCH_MESH.json")):
        if not os.path.exists(path):
            doc = {"mesh": shape, "devices": want_devs,
                   "actual_points": int(total_points)}
        else:
            with open(path) as f:
                doc = json.load(f)
            if (os.path.basename(path) == "BENCH_MESH.json"
                    and total_points < int(doc.get("actual_points",
                                                   -1))):
                log(f"clobber guard: {os.path.basename(path)} records "
                    f"a larger corpus; multiprocess leg not merged")
                continue
        doc["multiprocess"] = mp
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
        log(f"merged multiprocess leg into {os.path.basename(path)}")
    print(json.dumps(mp, indent=2))
    return 0


def run_mesh_bench(args) -> int:
    """BENCH_MESH.json: the unified-mesh-execution-plane batteries.

    Kernel-level over the synthesized corpus columns (mesh execution
    is a compute-plane property; the storage tiers feed it the same
    flat columns either way):

    - FOLD battery: rollup window fold over every series, sharded
      across the mesh (rollup/summary.window_summaries_sharded ->
      parallel/sharded.sharded_window_fold) vs a 1-device-mesh
      control — wall time both legs, result compared BYTE-for-byte
      (series never split shards; the combine is an all_gather), plus
      the float64 host fold for reference.
    - DASHBOARD battery: fused downsample+group reductions
      (sum/avg/dev moments and an exact p95) sharded over the mesh vs
      the single-device kernels — wall time + parity (f32 tolerance
      for moments; a dense integer-valued leg is compared
      byte-for-byte, the exactness argument of the gloo smoke).
    - EXPERT battery: one mixed moment+percentile dashboard batch
      through parallel/expert.run_dashboard_batch vs the serial
      kernel loop.
    """
    shape = args.mesh.strip().lower()
    if "x" in shape:
        r_s, _, c_s = shape.partition("x")
        want_devs = int(r_s) * int(c_s)
    else:
        want_devs = int(shape)
    if args.cpu or os.environ.get("JAX_PLATFORMS", "") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{want_devs}").strip()
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from opentsdb_tpu.parallel.compile import (cache_info,
                                               set_mesh_devices)
    from opentsdb_tpu.parallel.mesh import make_mesh
    from opentsdb_tpu.parallel.plan import (build_mesh,
                                            flatten_series_mesh)
    from opentsdb_tpu.parallel.sharded import (
        pack_shards,
        sharded_downsample_group,
        sharded_downsample_quantile,
    )
    from opentsdb_tpu.ops import kernels
    from opentsdb_tpu.rollup import summary
    from opentsdb_tpu.parallel import expert

    mesh = flatten_series_mesh(build_mesh(shape))
    D = int(mesh.devices.size)
    set_mesh_devices(D)
    one = make_mesh(1, devices=mesh.devices.reshape(-1)[:1])
    log(f"mesh: {shape} -> {D} devices "
        f"({mesh.devices.reshape(-1)[0].platform})")

    base = 1356998400
    pps = max(args.points // args.series, 1)
    step = max(args.span // pps, 1)
    log(f"synthesizing {args.series} series x {pps} points "
        f"(step {step}s)...")
    t0 = time.perf_counter()
    series, rng = _synth_mesh_corpus(args.series, pps, step)
    synth_s = time.perf_counter() - t0
    total_points = args.series * pps

    out = {"mesh": shape, "devices": D,
           "platform": str(mesh.devices.reshape(-1)[0].platform),
           "target_points": args.points,
           "actual_points": int(total_points),
           "series": args.series, "span_s": args.span,
           "synth_s": round(synth_s, 2),
           "host": {"cores": os.cpu_count()}}

    def timed(fn, repeats=3):
        fn()                        # warm (compile)
        best = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = fn()
            best.append(time.perf_counter() - t0)
        return r, min(best)

    # -- FOLD battery ------------------------------------------------
    res = 3600
    log("fold battery (sharded rollup window fold)...")
    fold_mesh, t_mesh = timed(
        lambda: summary.window_summaries_sharded(series, res, mesh))
    fold_one, t_one = timed(
        lambda: summary.window_summaries_sharded(series, res, one))
    byte_ok = all(
        np.array_equal(wa, wb) and ra.tobytes() == rb.tobytes()
        for (wa, ra), (wb, rb) in zip(fold_one, fold_mesh))
    t0 = time.perf_counter()
    for ts, vals in series:
        summary.window_summaries(ts, vals, res)
    t_host = time.perf_counter() - t0
    out["fold"] = {
        "res_s": res,
        "mesh_s": round(t_mesh, 3),
        "single_device_s": round(t_one, 3),
        "speedup": round(t_one / max(t_mesh, 1e-9), 2),
        "host_float64_s": round(t_host, 3),
        "byte_identical_vs_control": bool(byte_ok),
    }
    log(f"  fold: mesh {t_mesh:.3f}s vs 1-dev {t_one:.3f}s "
        f"(host f64 {t_host:.3f}s), byte_ok={byte_ok}")
    assert byte_ok, "sharded fold diverged from single-device control"
    del fold_mesh, fold_one

    # -- DASHBOARD battery -------------------------------------------
    interval = 3600
    B = args.span // interval
    log("dashboard battery (sharded reductions)...")
    packed = pack_shards(series, D)
    ts_d, vals_d, sid_d, valid_d, sps = packed
    packed1 = pack_shards(series, 1)
    ts_1, vals_1, sid_1, valid_1, sps1 = packed1
    dash = {}
    for agg_down, agg_group, label in (
            ("avg", "sum", "sum-of-avg"),
            ("sum", "max", "max-of-sum"),
            ("avg", "dev", "dev-of-avg")):
        def mesh_leg():
            gv, gm = sharded_downsample_group(
                ts_d, vals_d, sid_d, valid_d, mesh=mesh,
                series_per_shard=sps, num_buckets=B,
                interval=interval, agg_down=agg_down,
                agg_group=agg_group)
            return np.asarray(gv), np.asarray(gm)

        def ctrl_leg():
            gv, gm = sharded_downsample_group(
                ts_1, vals_1, sid_1, valid_1, mesh=one,
                series_per_shard=sps1, num_buckets=B,
                interval=interval, agg_down=agg_down,
                agg_group=agg_group)
            return np.asarray(gv), np.asarray(gm)

        (gv_m, gm_m), tm = timed(mesh_leg)
        (gv_c, gm_c), tc = timed(ctrl_leg)
        assert (gm_m == gm_c).all()
        # ELEMENTWISE relative diff (floored at |1.0| so near-zero
        # buckets read as absolute error) — a max|diff|/max|control|
        # ratio would let one small bucket be 100% wrong while a big
        # bucket hides it.
        rel = float((np.abs(gv_m[gm_m] - gv_c[gm_c])
                     / np.maximum(np.abs(gv_c[gm_c]), 1.0)).max()) \
            if gm_c.any() else 0.0
        assert rel < 1e-4, (label, rel)
        dash[label] = {"mesh_s": round(tm, 4),
                       "single_device_s": round(tc, 4),
                       "speedup": round(tc / max(tm, 1e-9), 2),
                       "max_rel_diff": rel}
        log(f"  {label}: mesh {tm:.4f}s vs 1-dev {tc:.4f}s "
            f"(rel diff {rel:.2e})")

    def p95_mesh():
        gv, gm = sharded_downsample_quantile(
            ts_d, vals_d, sid_d, valid_d,
            np.array([0.95], np.float32), mesh=mesh,
            series_per_shard=sps, num_buckets=B, interval=interval,
            agg_down="avg")
        return np.asarray(gv[0]), np.asarray(gm)

    def p95_ctrl():
        gv, gm = sharded_downsample_quantile(
            ts_1, vals_1, sid_1, valid_1,
            np.array([0.95], np.float32), mesh=one,
            series_per_shard=sps1, num_buckets=B, interval=interval,
            agg_down="avg")
        return np.asarray(gv[0]), np.asarray(gm)

    (qv_m, qm_m), tqm = timed(p95_mesh)
    (qv_c, qm_c), tqc = timed(p95_ctrl)
    assert (qm_m == qm_c).all()
    np.testing.assert_allclose(qv_m[qm_m], qv_c[qm_c], rtol=1e-5,
                               atol=1e-4)
    dash["p95-of-avg"] = {"mesh_s": round(tqm, 4),
                          "single_device_s": round(tqc, 4),
                          "speedup": round(tqc / max(tqm, 1e-9), 2)}
    log(f"  p95-of-avg: mesh {tqm:.4f}s vs 1-dev {tqc:.4f}s")

    # Dense integer byte-parity leg (the gloo smoke's exactness
    # argument, at bench scale): every contribution an exact integer,
    # so mesh width cannot change a bit.
    int_series = _synth_int_corpus(rng, min(args.series, 256), B,
                                   interval)
    pi = pack_shards(int_series, D)
    p1 = pack_shards(int_series, 1)
    gv_i, gm_i = sharded_downsample_group(
        pi[0], pi[1], pi[2], pi[3], mesh=mesh, series_per_shard=pi[4],
        num_buckets=B, interval=interval, agg_down="sum",
        agg_group="sum")
    gv_i1, gm_i1 = sharded_downsample_group(
        p1[0], p1[1], p1[2], p1[3], mesh=one, series_per_shard=p1[4],
        num_buckets=B, interval=interval, agg_down="sum",
        agg_group="sum")
    int_byte_ok = (np.asarray(gv_i).tobytes()
                   == np.asarray(gv_i1).tobytes())
    assert int_byte_ok
    dash["integer_sum_byte_identical"] = bool(int_byte_ok)
    out["dashboard"] = dash

    # -- EXPERT battery ----------------------------------------------
    log("expert battery (mixed dashboard batch)...")
    S_e, B_e = 64, min(B, 256)
    n_e = min(pps, 20_000)

    def subq(fam, agg=None, qn=None, dsagg="avg", seed=0):
        r = np.random.default_rng(100 + seed)
        ts = r.integers(0, B_e * interval, n_e).astype(np.int32)
        vals = r.normal(50, 9, n_e).astype(np.float32)
        sid = r.integers(0, S_e, n_e).astype(np.int32)
        d = {"family": fam, "ts": ts, "vals": vals, "sid": sid,
             "dsagg": dsagg}
        if fam == "moment":
            d["agg"] = agg
        else:
            d["quantile"] = qn
        return d

    batch = [subq("moment", agg="sum", seed=0),
             subq("moment", agg="avg", dsagg="max", seed=1),
             subq("percentile", qn=0.95, seed=2),
             subq("moment", agg="dev", seed=3),
             subq("percentile", qn=0.5, seed=4),
             subq("moment", agg="max", seed=5)]

    def expert_leg():
        return expert.run_dashboard_batch(
            batch, mesh, num_series=S_e, num_buckets=B_e,
            interval=interval)

    def serial_leg():
        outs = []
        for q in batch:
            o = kernels.downsample_group(
                q["ts"], q["vals"], q["sid"],
                np.ones(n_e, bool), num_series=S_e,
                num_buckets=B_e, interval=interval,
                agg_down=q["dsagg"], agg_group=q.get("agg", "count"))
            gm = np.asarray(o["group_mask"])
            if q["family"] == "moment":
                outs.append((np.asarray(o["group_values"]), gm))
            else:
                filled, in_range = kernels.gap_fill(
                    o["series_values"], o["series_mask"], B_e)
                outs.append((np.asarray(
                    kernels.masked_quantile_axis0(
                        filled, in_range,
                        np.array([q["quantile"]],
                                 np.float32))[0]), gm))
        return outs

    got_e, te = timed(expert_leg)
    got_s, ts_serial = timed(serial_leg)
    for (gv, gm), (wv, wm) in zip(got_e, got_s):
        assert (np.asarray(gm) == wm).all()
        np.testing.assert_allclose(np.asarray(gv)[wm], wv[wm],
                                   rtol=1e-4, atol=1e-3)
    out["expert"] = {"batch": len(batch),
                     "points_per_subquery": n_e,
                     "expert_s": round(te, 4),
                     "serial_s": round(ts_serial, 4),
                     "speedup": round(ts_serial / max(te, 1e-9), 2),
                     "answers_match_serial": True}
    log(f"  expert: batch {te:.4f}s vs serial {ts_serial:.4f}s")

    out["compile_cache"] = cache_info()
    out["iso"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())

    suffixed = os.path.join(
        REPO, f"BENCH_MESH_{total_points // 1_000_000}M_"
              f"{shape.replace('x', 'x')}.json")
    with open(suffixed, "w") as f:
        json.dump(out, f, indent=2)
    canonical = os.path.join(REPO, "BENCH_MESH.json")
    prev_pts = -1
    if os.path.exists(canonical):
        try:
            with open(canonical) as f:
                prev_pts = int(json.load(f).get("actual_points", -1))
        except Exception:
            prev_pts = -1
    if total_points >= prev_pts:
        with open(canonical, "w") as f:
            json.dump(out, f, indent=2)
        log(f"wrote BENCH_MESH.json ({total_points:,} points, "
            f"mesh {shape})")
    else:
        log(f"clobber guard: BENCH_MESH.json records {prev_pts:,} "
            f"points; this run kept in {os.path.basename(suffixed)}")
    return 0


def main() -> int:
    if os.environ.get("MESHBENCH_PROC_ID") is not None:
        return _fleet_child()      # fleet role: env-dispatched child
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=1_000_000_000)
    ap.add_argument("--series", type=int, default=2_000)
    ap.add_argument("--span", type=int, default=365 * 86400)
    ap.add_argument("--block", type=int, default=5_000,
                    help="points per series per time block (the "
                         "time-major interleave granularity)")
    ap.add_argument("--rss-cap-gb", type=float, default=100.0)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="spill memtable->sstable + truncate WAL every N "
                         "ingested points (0=only at end) — the "
                         "steady-state daemon shape: bounded RSS and "
                         "bounded recovery time under sustained ingest")
    ap.add_argument("--shards", type=int, default=0,
                    help="series-shard the store N ways "
                         "(storage/sharded.py): per-shard WALs and "
                         "sstable tiers, parallel checkpoint spills, "
                         "staggered tiered collapses. Any explicit "
                         "value (1 included) writes a _S<N>-suffixed "
                         "artifact; the default keeps the legacy "
                         "single-store naming")
    ap.add_argument("--rollup", action="store_true",
                    help="maintain the materialized rollup tier "
                         "(opentsdb_tpu/rollup/) during ingest and "
                         "record long-range query latency raw vs "
                         "rollup into BENCH_ROLLUP.json (both legs on "
                         "this host/config)")
    ap.add_argument("--repeat-queries", action="store_true",
                    help="record the query fast path into "
                         "BENCH_QCACHE.json: a warm-dashboard leg "
                         "(cold vs warm repeat-query latency through "
                         "the executor's fragment cache, byte-exact "
                         "answer check) plus mid-ingest dirty-set "
                         "derivation probes (incremental store index "
                         "vs the legacy full memtable-key sweep). "
                         "Writes _Q-suffixed scale artifacts so plain "
                         "runs are never clobbered")
    ap.add_argument("--codec", default=None, choices=("tsst4",),
                    help="run the compressed-columnar comparison "
                         "instead of the plain scale run: build the "
                         "corpus TWICE (sstable_codec=none control, "
                         "then tsst4), measure on-disk footprint, "
                         "ingest dps, cold 1-week scan, warm "
                         "dashboard, and the fused decode-aggregate "
                         "vs decode-then-reduce downsample battery; "
                         "writes BENCH_COMPRESS.json (+ a size-"
                         "suffixed _C artifact — plain scale "
                         "artifacts are never touched)")
    ap.add_argument("--fused-battery", action="store_true",
                    help="with --codec: extend the corpus with a "
                         "second low-cardinality tag dimension and an "
                         "int-valued sibling metric, and add tag-"
                         "filtered, group-by, and TSINT rows to the "
                         "fused battery (fused vs decode-then-reduce "
                         "on the same host; TSINT rows checked "
                         "bit-for-bit)")
    ap.add_argument("--sketch-serve", action="store_true",
                    help="run the accuracy-budgeted approximate-"
                         "serving comparison instead of the plain "
                         "scale run: one rollup-backed corpus with "
                         "digest + moment sketch columns, then the "
                         "pNN dashboard battery raw-forced vs "
                         "digest-served vs moment-served (wall time, "
                         "reported vs actual error, within-bounds "
                         "check), per-kind tier bytes, and the "
                         "Storyboard allocation at three byte "
                         "budgets; writes BENCH_SKETCH.json")
    ap.add_argument("--ingest-battery", action="store_true",
                    help="run the ingest fast-path comparison instead "
                         "of the plain scale run: one telnet-format "
                         "corpus through decode_puts -> ingest_batch "
                         "with durable acks on an fsync=True store, "
                         "legs crossing group-commit on/off x delta-"
                         "vs-full rollup folds x codec none/tsst4 "
                         "(plus the PR-19 scalar-decode baseline and "
                         "a decode micro-bench), every leg's served "
                         "1h answer fingerprint-checked identical; "
                         "writes BENCH_INGEST.json (clobber-guarded, "
                         "+ a size/shard-suffixed artifact)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="hostile-workload profile (ISSUE 14): spread "
                         "the series over N tenant ids so the timed "
                         "ingest pays per-tenant cardinality "
                         "accounting (opentsdb_tpu/tenant/) in the "
                         "hot path; the artifact records the "
                         "accounting snapshot (tenant count, tiers, "
                         "TENANTS.json bytes). 0 = single default "
                         "tenant (accounting still on unless "
                         "--no-tenant-accounting)")
    ap.add_argument("--no-tenant-accounting", action="store_true",
                    help="disable tenant accounting entirely — the "
                         "control leg for measuring the accounting "
                         "tax on ingest dps")
    ap.add_argument("--workdir", default="/tmp/tsdb_scale")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="mesh execution plane battery: 'N' or 'RxC'. "
                         "Runs the sharded rollup-fold and dashboard-"
                         "reduction batteries over the synthesized "
                         "corpus, mesh vs single-device control, and "
                         "writes BENCH_MESH.json (+ a size/mesh-"
                         "suffixed artifact; the canonical file is "
                         "clobber-guarded by corpus size). With --cpu "
                         "the virtual device count is forced "
                         "automatically")
    ap.add_argument("--fleet", type=int, default=0,
                    help="with --mesh: run the MULTI-PROCESS leg "
                         "instead — N gloo processes (the served "
                         "deployment mode's plane bootstrap) split "
                         "the mesh width and the series axis, merged "
                         "fleet answers are checked vs the 1-device "
                         "control under the declared per-kernel "
                         "byte-or-tolerance contract, plus the live "
                         "grow/shrink reshard-under-ingest probe; "
                         "merges a 'multiprocess' section into "
                         "BENCH_MESH.json")
    args = ap.parse_args()

    if args.mesh:
        if args.fleet and args.fleet > 1:
            return run_mesh_fleet_bench(args)
        return run_mesh_bench(args)
    if args.codec or args.fused_battery:
        return run_codec_compare(args)
    if args.sketch_serve:
        return run_sketch_serve(args)
    if args.ingest_battery:
        return run_ingest_battery(args)

    dev = _jax_setup(args)

    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.storage.sharded import ShardedKVStore
    from opentsdb_tpu.utils.config import Config
    from opentsdb_tpu.utils.gctune import tune_for_ingest
    from opentsdb_tpu.utils.nativeext import ext as native_ext
    import opentsdb_tpu.core.codec_np as codec_np

    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    wal = os.path.join(args.workdir, "wal")
    if args.shards > 1:
        store = ShardedKVStore(args.workdir, shards=args.shards)
        wal_paths = [s._wal_path for s in store.shards]
    else:
        store = MemKVStore(wal_path=wal)
        wal_paths = [wal]

    def wal_bytes() -> int:
        return sum(os.path.getsize(p) for p in wal_paths
                   if os.path.exists(p))

    cfg = Config(auto_create_metrics=True, wal_path=wal,
                 shards=max(args.shards, 1),
                 enable_rollups=args.rollup, rollup_catchup="sync",
                 tenant_accounting=not args.no_tenant_accounting)
    tsdb = TSDB(store, cfg, start_compaction_thread=False)
    tune_for_ingest()

    base = 1356998400
    pps = max(args.points // args.series, 1)     # points per series
    step = max(args.span // pps, 1)
    block = min(args.block, pps)
    rng = np.random.default_rng(7)

    out = {"device": str(dev), "target_points": args.points,
           "shards": args.shards,
           "series": args.series, "span_s": args.span,
           "points_per_series": pps, "step_s": step,
           "block_points": block, "workload": "time-major",
           "native_ext": native_ext is not None,
           "host": {"cores": os.cpu_count(),
                    "ram_gb": round(os.sysconf("SC_PAGE_SIZE")
                                    * os.sysconf("SC_PHYS_PAGES")
                                    / (1 << 30))}}

    attr = Attribution()
    attr.wrap(tsdb.store, "put_many_columnar", "kv.put_batch")
    if hasattr(tsdb.store, "_wal_append_batch_columnar"):
        attr.wrap(tsdb.store, "_wal_append_batch_columnar", "kv.wal",
                  nested_in="kv.put_batch")
    elif hasattr(tsdb.store, "shards"):
        # Sharded store: the WAL writes happen inside each shard; all
        # shards accumulate into the one kv.wal label.
        for s in tsdb.store.shards:
            attr.wrap(s, "_wal_append_batch_columnar", "kv.wal",
                      nested_in="kv.put_batch")
    if tsdb.devwindow is not None:
        attr.wrap(tsdb.devwindow, "append", "devwindow.append")
    attr.wrap(tsdb, "_observe", "sketch.observe")
    attr.wrap(codec_np, "encode_cells_multi", "codec.encode")
    attr.wrap(codec_np, "sort_dedup", "codec.sort_dedup")

    # Per-series fixed phase jitter (vectorized synthesis reuses one
    # value template per block; per-point rng per series would put
    # synthesis back on the critical path).
    phase = rng.integers(0, max(step - 1, 1), size=args.series)
    tags_by_series = [{"host": f"h{si:04d}"} for si in range(args.series)]

    total = 0
    peak_rss = 0.0
    ceiling = None
    synth_s = 0.0
    mid_ckpts: list[dict] = []
    next_ckpt = args.checkpoint_every or (1 << 62)

    # Live-ingest dirty-set probes (--repeat-queries): time BOTH
    # derivations of the rollup planner's dirty-window source at
    # increasing memtable fills — the store's incremental index
    # (storage/kv dirty_bases) vs the legacy full pending-key sweep —
    # so the artifact shows which one scales with memtable size.
    dirty_probes: list[dict] = []
    probe_marks = ([max(int(args.points * f), 1)
                    for f in (0.01, 0.03, 0.05, 0.5, 1.0)]
                   if args.repeat_queries else [])

    def probe_dirty(at_points: int) -> None:
        from opentsdb_tpu.core.const import TIMESTAMP_BYTES, UID_WIDTH
        lo, hi = UID_WIDTH, UID_WIDTH + TIMESTAMP_BYTES
        store, table = tsdb.store, tsdb.table
        t0 = time.perf_counter()
        inc = store.dirty_bases(table)
        t_inc = time.perf_counter() - t0
        t0 = time.perf_counter()
        store.dirty_bases(table)
        t_inc_cached = time.perf_counter() - t0
        t0 = time.perf_counter()
        keys = [k for k in store.pending_keys(table) if len(k) >= hi]
        blob = b"".join(k[lo:hi] for k in keys)
        swept = (np.unique(np.frombuffer(blob, ">u4").astype(np.int64))
                 if keys else np.empty(0, np.int64))
        t_sweep = time.perf_counter() - t0
        ck = ckpt["thread"]
        if ck is None or not ck.is_alive():
            # Only comparable when no overlapped spill can mutate the
            # set between the two (unsynchronized) derivations.
            assert np.array_equal(inc, swept), \
                "incremental dirty set diverged from sweep"
        rec = {"at_points": at_points, "pending_keys": len(keys),
               "dirty_bases": int(len(inc)),
               "incremental_s": round(t_inc, 6),
               "incremental_cached_s": round(t_inc_cached, 6),
               "sweep_s": round(t_sweep, 6)}
        dirty_probes.append(rec)
        log(f"  dirty probe @ {at_points:,}: {rec}")

    # GC pause attribution: the collector's stop-the-world time is part
    # of the unattributed wall unless measured directly.
    gc_acc = {"s": 0.0, "t0": 0.0}

    def _gc_cb(phase, info):
        if phase == "start":
            gc_acc["t0"] = time.perf_counter()
        else:
            gc_acc["s"] += time.perf_counter() - gc_acc["t0"]

    gc.callbacks.append(_gc_cb)

    # Overlapped checkpoints (VERDICT r04 item 3): the 3-phase spill
    # design only locks briefly at freeze/swap, so the phase-2 sstable
    # write runs on this thread WHILE ingest continues — on the 1-core
    # host the win is the hidden IO/fsync wait, and ingest only blocks
    # when the next trigger fires before the previous spill finished
    # (counted as checkpoint.wait).
    ckpt = {"thread": None, "wait_s": 0.0, "spill_s": 0.0,
            "error": None}

    def _ckpt_join():
        t = ckpt["thread"]
        if t is not None and t.is_alive():
            t0 = time.perf_counter()
            t.join()
            blocked = time.perf_counter() - t0
            ckpt["wait_s"] += blocked
            # The blocked join is the pause ingest actually OBSERVES
            # mid-checkpoint (the spill itself is overlapped); record
            # it on the checkpoint that caused it so worst-single-pause
            # is in the artifact, not just the sum.
            if mid_ckpts:
                mid_ckpts[-1]["blocked_s"] = round(blocked, 1)
        ckpt["thread"] = None
        if ckpt["error"] is not None:
            # A swallowed spill failure would publish an artifact whose
            # dps/attribution silently undercount checkpoint cost.
            raise RuntimeError("mid-run checkpoint failed") \
                from ckpt["error"]

    def _ckpt_run(at_points: int) -> None:
        t0 = time.perf_counter()
        try:
            rows = tsdb.checkpoint()
        except BaseException as e:
            ckpt["error"] = e
            ckpt["spill_s"] += time.perf_counter() - t0
            raise
        wall = time.perf_counter() - t0
        ckpt["spill_s"] += wall
        mid_ckpts.append({
            "at_points": at_points, "wall_s": round(wall, 1),
            "rows_spilled": rows, "overlapped": True,
            "rss_gb_after": round(rss_gb(), 1)})
        log(f"  mid-run checkpoint @ {at_points:,}: {mid_ckpts[-1]}")

    t_ingest = time.perf_counter()
    last_log = t_ingest
    stop = False
    done_pps = 0          # per-series points actually ingested
    # An ingest failure (or a failed overlapped spill surfacing at the
    # next trigger) must still join the spill thread — never abandon it
    # mid-write — and uninstall the process-global GC callback (a leak
    # for any embedder retrying after the exception).
    try:
        for boff in range(0, pps, block):
            bn = min(block, pps - boff)
            # --- synthesis (excluded from attribution, counted in wall +
            # reported separately) ---
            t0 = time.perf_counter()
            rel = (boff + np.arange(bn, dtype=np.int64)) * step
            template = (np.cumsum(rng.normal(0, 1, bn).astype(np.float32))
                        + 100.0)
            blocks = []
            for si in range(args.series):
                blocks.append((base + rel + phase[si],
                               template + np.float32(si)))
            synth_s += time.perf_counter() - t0
            # --- timed time-major ingest: every series advances through
            # this block before any series sees the next one ---
            for si in range(args.series):
                ts, vals = blocks[si]
                total += tsdb.add_batch(
                    "scale.metric", ts, vals, tags_by_series[si],
                    tenant=(f"t{si % args.tenants}" if args.tenants
                            else "default"))
                if total >= next_ckpt:
                    _ckpt_join()  # previous spill must land first
                    t = threading.Thread(target=_ckpt_run, args=(total,),
                                         daemon=True)
                    ckpt["thread"] = t
                    t.start()
                    next_ckpt = total + args.checkpoint_every
                if probe_marks and total >= probe_marks[0]:
                    while probe_marks and total >= probe_marks[0]:
                        probe_marks.pop(0)
                    probe_dirty(total)
            now = time.perf_counter()
            r = rss_gb()
            peak_rss = max(peak_rss, r)
            if now - last_log > 30 or boff + bn >= pps:
                log(f"  t+{boff + bn}/{pps} per series: {total:,} pts, "
                    f"{total / (now - t_ingest):,.0f} dps, rss {r:.1f} GB")
                last_log = now
            done_pps = boff + bn
            if r > args.rss_cap_gb:
                ceiling = f"RSS {r:.1f} GB > cap {args.rss_cap_gb} GB"
                log(f"  stopping early: {ceiling}")
                stop = True
            if stop:
                break
        _ckpt_join()  # an in-flight spill is part of the ingest story
    finally:
        t = ckpt["thread"]
        if t is not None and t.is_alive():
            t.join()
        gc.callbacks.remove(_gc_cb)
    if tsdb.devwindow is not None:
        tsdb.devwindow.flush()
    if tsdb.sketches is not None:
        tsdb.sketches.flush()
    ingest_s = time.perf_counter() - t_ingest
    peak_rss = max(peak_rss, rss_gb())
    out["ingest"] = {
        "points": total, "wall_s": round(ingest_s, 1),
        "dps": round(total / ingest_s),
        "synth_s": round(synth_s, 1),
        "dps_ex_synth": round(total / max(ingest_s - synth_s, 1e-9)),
        "dps_between_checkpoints": round(
            total / max(ingest_s - synth_s - ckpt["wait_s"], 1e-9)),
        "peak_rss_gb": round(peak_rss, 1),
        "ceiling": ceiling or "target reached"}
    # Checkpoint + GC lines so the attribution sums to the wall
    # (VERDICT r04: 79 s of a 153 s wall was unattributed — mostly the
    # synchronous checkpoints the table omitted). The overlapped spill
    # wall is reported nested: it runs concurrently, so only the
    # blocked join time (checkpoint.wait) is wall the ingest loop lost
    # outright; the GIL/CPU the spill thread steals from ingest shows
    # up inside the other lines' own timings.
    attr.acc["checkpoint.spill"] = ckpt["spill_s"]
    attr.nested.add("checkpoint.spill")
    attr.acc["checkpoint.wait"] = ckpt["wait_s"]
    attr.acc["gc"] = gc_acc["s"]
    out["ingest"]["attribution"] = attr.table(ingest_s - synth_s)
    if mid_ckpts:
        out["ingest"]["worst_ckpt_blocked_s"] = max(
            m.get("blocked_s", 0.0) for m in mid_ckpts)
        out["ingest"]["worst_ckpt_wall_s"] = max(
            m["wall_s"] for m in mid_ckpts)
    out["wal_bytes"] = wal_bytes()
    if tsdb.tenants is not None:
        # The hostile-workload profile's accounting story: what the
        # control plane cost to keep (snapshot bytes, tier split)
        # rides the same artifact as the dps it may have taxed.
        info = tsdb.tenants.snapshot_info()
        tiers: dict = {}
        for ent in info["tenants"].values():
            tiers[ent["tier"]] = tiers.get(ent["tier"], 0) + 1
        out["tenant_accounting"] = {
            "tenants": len(info["tenants"]),
            "tracked_series": info["tracked_series"],
            "tiers": tiers,
            "snapshots_written": info["snapshots_written"],
            "state_bytes": (os.path.getsize(tsdb.tenants.path)
                            if tsdb.tenants.path
                            and os.path.exists(tsdb.tenants.path)
                            else 0),
        }
    elif args.no_tenant_accounting:
        out["tenant_accounting"] = {"disabled": True}
    if mid_ckpts:
        out["mid_checkpoints"] = mid_ckpts
    log(f"ingested {total:,} in {ingest_s:,.0f}s "
        f"({total/ingest_s:,.0f} dps, ex-synth "
        f"{out['ingest']['dps_ex_synth']:,} dps), wal "
        f"{out['wal_bytes']/(1<<30):.2f} GB")
    log(f"attribution: {out['ingest']['attribution']}")

    # Honest horizon: an RSS-ceiling early stop ingested only
    # done_pps points per series — query/report against THAT extent,
    # not the untouched target (which would fabricate cold-scan
    # points/s over data that was never written).
    end = base + done_pps * step
    # Device-window behavior under the budget.
    dw = tsdb.devwindow
    mw = None
    if dw is not None:
        muid = tsdb.metrics.get_id("scale.metric")
        mw = dw._metrics.get(muid)
        out["devwindow"] = {
            "max_points_budget": dw.max_points,
            "appended": dw.appended_points,
            "evicted": dw.evicted_points,
            "resident": dw._total_points,
            "complete_from": (mw.complete_from if mw else None),
            "coverage_tail_s": (
                None if mw is None or mw.complete_from is None
                else end - mw.complete_from),
            "dirty": bool(mw.dirty) if mw else None,
        }
        log(f"devwindow: {out['devwindow']}")

    # Queries at scale.
    ex = QueryExecutor(tsdb, backend="tpu")
    q = {}
    if mw is not None and not mw.dirty:
        rstart = mw.complete_from if mw.complete_from else base
        spec = QuerySpec("scale.metric", {}, "sum",
                         downsample=(3600, "avg"))
        ex.run(spec, rstart, end)  # warm
        t0 = time.perf_counter()
        ex.run(spec, rstart, end)
        q["resident_sum_s"] = time.perf_counter() - t0
        p95 = QuerySpec("scale.metric", {}, "p95",
                        downsample=(3600, "avg"))
        ex.run(p95, rstart, end)
        t0 = time.perf_counter()
        ex.run(p95, rstart, end)
        q["resident_p95_s"] = time.perf_counter() - t0
        q["resident_range_s"] = end - rstart
        q["resident_hits"] = dw.window_hits
    # Cold scan path (devwindow detached): 1 day and 1 week.
    dwx, tsdb.devwindow = tsdb.devwindow, None
    try:
        for label, span in (("1day", 86400), ("1week", 7 * 86400)):
            spec = QuerySpec("scale.metric", {}, "sum",
                             downsample=(3600, "avg"))
            t0 = time.perf_counter()
            ex.run(spec, end - span, end)
            dt = time.perf_counter() - t0
            span_covered = min(span, done_pps * step)
            npts = int(span_covered // step) * args.series
            q[f"cold_scan_{label}_s"] = dt
            q[f"cold_scan_{label}_points"] = npts
            q[f"cold_scan_{label}_pts_per_s"] = round(npts / dt)
    finally:
        tsdb.devwindow = dwx
    # Streaming sketch quantiles over every series.
    if tsdb.sketches is not None:
        ex.sketch_quantiles("scale.metric", {}, [0.5, 0.99])
        t0 = time.perf_counter()
        ex.sketch_quantiles("scale.metric", {}, [0.5, 0.99])
        q["sketch_quantile_s"] = time.perf_counter() - t0
    out["queries"] = {k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in q.items()}
    log(f"queries: {out['queries']}")

    # Checkpoint: memtable -> sstable spill + WAL truncation.
    t0 = time.perf_counter()
    rows = tsdb.checkpoint()
    out["checkpoint"] = {
        "wall_s": round(time.perf_counter() - t0, 1),
        "rows_spilled": rows,
        "dir_bytes": du(args.workdir),
        "wal_bytes_after": wal_bytes(),
    }
    log(f"checkpoint: {out['checkpoint']}")

    # Warm-dashboard leg (--repeat-queries): repeat-query latency cold
    # (fragment cache cleared) vs warm (second+ run) on the spilled
    # corpus, byte-exact answer check. Devwindow and rollups detached
    # so the legs measure the FRAGMENT cache's scan-path win, per leg:
    # jit/uid warmup on a same-span shifted range first, so "cold" is
    # the scan+decode cost, not compilation.
    if args.repeat_queries:
        rq: dict = {
            "chunk_s": int(getattr(tsdb.config, "qcache_chunk_s", 0)),
            "qcache_points": int(getattr(tsdb.config, "qcache_points",
                                         0))}
        dwx, tsdb.devwindow = tsdb.devwindow, None
        hold_roll = getattr(tsdb, "rollups", None)
        tsdb.rollups = None
        try:
            exq = QueryExecutor(tsdb, backend="tpu")
            legs = [
                ("1day_1h_sum", 86400,
                 QuerySpec("scale.metric", {}, "sum",
                           downsample=(3600, "avg"))),
                ("1week_1h_sum", 7 * 86400,
                 QuerySpec("scale.metric", {}, "sum",
                           downsample=(3600, "avg"))),
                ("1week_1h_p95", 7 * 86400,
                 QuerySpec("scale.metric", {}, "p95",
                           downsample=(3600, "avg"))),
                # Tag-filtered panel: exercises the series-hint fan-out
                # pruning too (shard routing + sstable blooms).
                ("1week_1h_host0", 7 * 86400,
                 QuerySpec("scale.metric", {"host": "h0000"}, "sum",
                           downsample=(3600, "avg"))),
            ]
            for label, span, spec in legs:
                if span * 2 > done_pps * step:
                    continue
                lo = end - span
                exq.run(spec, lo - span, end - span)   # jit/uid warm
                exq._frag_cache.clear()
                t0 = time.perf_counter()
                r_cold, plan_c, cached_c = exq.run_with_plan(
                    spec, lo, end)
                t_cold = time.perf_counter() - t0
                warms = []
                r_warm = r_cold
                cached_w = False
                for _ in range(3):
                    t0 = time.perf_counter()
                    r_warm, _plan, cached_w = exq.run_with_plan(
                        spec, lo, end)
                    warms.append(time.perf_counter() - t0)
                t_warm = sorted(warms)[len(warms) // 2]
                ident = (len(r_cold) == len(r_warm) and all(
                    np.array_equal(a.timestamps, b.timestamps)
                    and np.array_equal(a.values, b.values)
                    for a, b in zip(r_cold, r_warm)))
                rq[label] = {
                    "cold_s": round(t_cold, 4),
                    "warm_s": round(t_warm, 4),
                    "warm_all_s": [round(w, 4) for w in warms],
                    "speedup": round(t_cold / max(t_warm, 1e-9), 1),
                    "plan": plan_c, "warm_cached": bool(cached_w),
                    "byte_identical": bool(ident)}
                log(f"qcache {label}: cold {t_cold:.3f}s -> warm "
                    f"{t_warm:.3f}s "
                    f"({t_cold / max(t_warm, 1e-9):.1f}x, "
                    f"cached={cached_w}, identical={ident})")
            rq["counters"] = {
                "hits": exq.qcache_hits, "misses": exq.qcache_misses,
                "bypasses": exq.qcache_bypasses,
                "cached_points": exq._frag_cache.cost,
                "bloom_files_skipped": getattr(
                    tsdb.store, "bloom_files_skipped", 0),
                "bloom_shards_skipped": getattr(
                    tsdb.store, "bloom_shards_skipped", 0)}
        finally:
            tsdb.devwindow = dwx
            tsdb.rollups = hold_roll
        rq["dirty_probes"] = dirty_probes
        out["qcache"] = rq
        qart = {"device": str(dev), "shards": args.shards,
                "series": args.series, "points": total,
                "step_s": step, "span_s": done_pps * step,
                "native_ext": native_ext is not None,
                "host": out["host"], **rq}
        with open(os.path.join(REPO, "BENCH_QCACHE.json"), "w") as f:
            json.dump(qart, f, indent=2)
        log(f"qcache artifact: {qart}")

    # Rollup tier: long-range downsampled queries raw vs rollup on the
    # SAME host/config (both legs cold-path: devwindow detached), plus
    # what the tier cost to maintain. Written to BENCH_ROLLUP.json.
    if args.rollup and tsdb.rollups is not None:
        tsdb.rollups.wait_ready()
        rq: dict = {"resolutions": list(tsdb.rollups.resolutions),
                    "records": tsdb.rollups.records_written,
                    "folds": tsdb.rollups.folds}
        rq["tier_bytes"] = sum(
            du(d) for dirs in tsdb.rollups._dirs.values() for d in dirs)
        dwx, tsdb.devwindow = tsdb.devwindow, None
        try:
            for label, span, interval in (
                    ("1day_1h", 86400, 3600),
                    ("1week_1h", 7 * 86400, 3600),
                    ("1month_1d", 30 * 86400, 86400)):
                if span > done_pps * step:
                    continue
                spec = QuerySpec("scale.metric", {}, "sum",
                                 downsample=(interval, "avg"))
                lo = end - span
                ex.run(spec, lo, end)  # warm (jit + uid caches)
                t0 = time.perf_counter()
                r_roll = ex.run(spec, lo, end)
                troll = time.perf_counter() - t0
                plan = ex.last_plan
                hold, tsdb.rollups = tsdb.rollups, None
                try:
                    t0 = time.perf_counter()
                    r_raw = ex.run(spec, lo, end)
                    traw = time.perf_counter() - t0
                finally:
                    tsdb.rollups = hold
                same = (len(r_roll) == len(r_raw) and all(
                    np.array_equal(a.timestamps, b.timestamps)
                    and np.allclose(a.values, b.values,
                                    rtol=2e-4, atol=1e-3)
                    for a, b in zip(r_roll, r_raw)))
                rq[label] = {
                    "raw_s": round(traw, 4),
                    "rollup_s": round(troll, 4),
                    "speedup": round(traw / max(troll, 1e-9), 1),
                    "plan": plan, "answers_match": bool(same)}
                log(f"rollup {label}: raw {traw:.3f}s -> rollup "
                    f"{troll:.3f}s ({traw / max(troll, 1e-9):.1f}x, "
                    f"plan={plan}, match={same})")
        finally:
            tsdb.devwindow = dwx
        out["rollup"] = rq
        roll_art = {
            "device": str(dev), "shards": args.shards,
            "series": args.series, "points": total,
            "step_s": step, "span_s": done_pps * step,
            "native_ext": native_ext is not None,
            "host": out["host"], **rq}
        with open(os.path.join(REPO, "BENCH_ROLLUP.json"), "w") as f:
            json.dump(roll_art, f, indent=2)
        log(f"rollup artifact: {roll_art}")

    write_artifacts(out)
    print(json.dumps({"points": total,
                      "dps": round(total / ingest_s),
                      "dps_ex_synth": out["ingest"]["dps_ex_synth"],
                      "device": str(dev)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
