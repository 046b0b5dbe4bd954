#!/usr/bin/env python
"""Hostile-workload harness: adversarial scenario legs with correctness
gates; the report goes to ``--json``.

The benchmark's cells send uniform synthetic series; these legs are
the other half of the story — the workloads a hostile (or merely
broken) tenant actually sends:

  cardinality  millions of DISTINCT series: directory / UID / bloom /
               sketch-slot pressure, per-tenant accounting parity
               (exact tier and HLL tier), heavy-hitter attribution of
               the attacking namespace, and the tenant series limits
               refusing exactly the over-budget NEW series — every
               refusal declared (TenantLimitError), existing series
               still ingesting, snapshot round-trip exact.
  churn        series-churn cycles aging the fragment cache and the
               directory: delete half the rows, mint new series, and
               demand warm answers stay BYTE-identical to a cold
               executor's over every cycle.
  backfill     out-of-order backfill storms racing rollup folds
               (checkpoints interleave with writes into old windows):
               rollup-served answers must be bit-identical to raw
               scans for the whole aggregator battery.
  hot-tenant   one hot-key tenant hammering the replica that owns its
               series through a LIVE router (writer + 2 tailing
               replicas + router, one event loop): per-tenant query
               quota refusals all declared (429 + Retry-After), served
               answers byte-equal the writer's direct answer, a /fault
               delay on the owner replica makes hedges fire and win,
               and /api/topology attributes the slow replica's hop p95.

``--bug no-limit`` is the gate: TSDB_TENANT_BUG=no-limit silently
disables the series limiter, and the harness MUST flag the missing
refusals (a harness that can't catch a disabled limiter is theater).
Gate semantics mirror sketch_harness.py: with --bug the exit code is 0
iff violations WERE flagged.

    python scripts/hostile_harness.py [--legs a,b] [--series N]
        [--shards N] [--fast] [--bug no-limit] [--json OUT]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

T0 = 1_600_000_000 - 1_600_000_000 % 86400


def log(msg: str) -> None:
    print(msg, flush=True)


class Leg:
    """One scenario leg: measurements + correctness violations."""

    def __init__(self, name: str, workdir: str) -> None:
        self.name = name
        self.dir = os.path.join(workdir, name)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.t0 = time.time()
        self.stats: dict = {}
        self.checks = 0
        self.violations: list[dict] = []

    def check(self, ok: bool, what: str, **info) -> bool:
        self.checks += 1
        if not ok:
            self.violations.append(dict(what=what, **info))
            log(f"  VIOLATION [{self.name}] {what} {info}")
        return ok

    def done(self) -> dict:
        return {
            "leg": self.name,
            "wall_s": round(time.time() - self.t0, 2),
            "checks": self.checks,
            "violations": self.violations,
            **self.stats,
        }


def open_writer(dirpath: str, shards: int, **cfg_kw):
    """Writer TSDB with the hostile profile: cpu backend, compactions
    off (deterministic), small sketch compression (a million series at
    the default K=128 would hold ~1 GB of digest stacks — the leg is
    about DIRECTORY pressure, not digest accuracy)."""
    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.storage.sharded import ShardedKVStore
    from opentsdb_tpu.utils.config import Config

    kw = dict(wal_path=dirpath, shards=shards, backend="cpu",
              auto_create_metrics=True, enable_compactions=False,
              device_window=False, enable_sketches=True,
              sketch_compression=8, sketch_hll_p=8,
              sketch_flush_points=1 << 20)
    kw.update(cfg_kw)
    cfg = Config(**kw)
    if shards > 1:
        store = ShardedKVStore(dirpath, shards=shards)
    else:
        store = MemKVStore(wal_path=os.path.join(dirpath, "wal"))
    return TSDB(store, cfg, start_compaction_thread=False)


# ---------------------------------------------------------------------------
# Leg: cardinality — million-distinct-series pressure + limits
# ---------------------------------------------------------------------------

def leg_cardinality(args, workdir: str) -> dict:
    from opentsdb_tpu.core.errors import TenantLimitError
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
    from opentsdb_tpu.storage.sstable import series_hash
    from opentsdb_tpu.tenant.accounting import hll_rel_error

    leg = Leg("cardinality", workdir)
    S = args.series
    tenants = [f"t{i}" for i in range(max(args.tenants - 1, 1))]
    # The attacker floods 60% of the stream under its own namespace;
    # its limit admits only ~half of that, so a known number of NEW
    # series MUST refuse (exactly what --bug no-limit sabotages).
    attacker_share = 0.6
    attacker_tried = int(S * attacker_share)
    limit = max(attacker_tried // 2, 1)
    log(f"[cardinality] {S} series, {len(tenants) + 1} tenants, "
        f"attacker limit {limit}, shards={args.shards}")
    tsdb = open_writer(leg.dir, args.shards,
                       tenant_max_series=limit,
                       tenant_overrides=tuple(
                           f"{t}=0" for t in tenants))
    rng = np.random.default_rng(args.seed)
    tried: dict[str, int] = {}
    admitted: dict[str, int] = {}
    refused = 0
    undeclared = 0
    t_ing = time.time()
    val = np.asarray([1.0])
    for i in range(S):
        if i < attacker_tried:
            tenant = "attacker"
            metric = f"attack.flood.m{i % 8}"
        else:
            tenant = tenants[i % len(tenants)]
            metric = f"hostile.card.m{i % 8}"
        tried[tenant] = tried.get(tenant, 0) + 1
        ts = np.asarray([T0 + (i % 24) * 3600 + (i % 1800)], np.int64)
        try:
            tsdb.add_batch(metric, ts, val, {"id": str(i)},
                           tenant=tenant)
            admitted[tenant] = admitted.get(tenant, 0) + 1
        except TenantLimitError:
            refused += 1
        except Exception as e:  # any other refusal is NOT declared
            undeclared += 1
            if undeclared <= 3:
                log(f"  undeclared refusal: {e!r}")
        if args.fast and i and i % 10000 == 0:
            log(f"  ... {i}/{S}")
        elif not args.fast and i and i % 200000 == 0:
            log(f"  ... {i}/{S}")
    ingest_s = time.time() - t_ing
    leg.stats["series_tried"] = S
    leg.stats["series_admitted"] = sum(admitted.values())
    leg.stats["series_refused"] = refused
    leg.stats["register_series_per_s"] = round(S / ingest_s, 1)
    leg.stats["ingest_wall_s"] = round(ingest_s, 2)

    # --- limit refusals: every one declared, count exact (exact
    # tier) or within the declared HLL error (the attacker crossed
    # the cutoff, so the cap binds on the ESTIMATE — by design: that
    # is what bounds per-tenant accounting memory under this very
    # attack) ------------------------------------------------------------
    expected_refused = max(attacker_tried - limit, 0)
    leg.check(undeclared == 0, "undeclared-refusal",
              count=undeclared)
    acct = tsdb.tenants
    att_tier = acct.snapshot_info()["tenants"]["attacker"]["tier"]
    tol = (0 if att_tier == "exact"
           else int(3 * hll_rel_error(acct.hll_p) * limit) + 2)
    leg.check(abs(refused - expected_refused) <= tol,
              "limit-refusal-count",
              refused=refused, expected=expected_refused,
              tier=att_tier, tolerance=tol,
              hint="--bug no-limit trips exactly this check")
    # Existing series keep ingesting: re-put an attacker series that
    # was admitted before the limit hit.
    try:
        tsdb.add_batch("attack.flood.m0",
                       np.asarray([T0 + 86000], np.int64), val,
                       {"id": "0"}, tenant="attacker")
        leg.check(True, "existing-series-ingests")
    except Exception as e:
        leg.check(False, "existing-series-ingests", error=repr(e))

    # --- accounting parity vs the exact oracle ---------------------------
    acct = tsdb.tenants
    info = acct.snapshot_info(tsdb.tenant_limits)
    err3 = 3 * hll_rel_error(acct.hll_p)
    for tenant, true in admitted.items():
        ent = info["tenants"].get(tenant)
        if not leg.check(ent is not None, "tenant-missing",
                         tenant=tenant):
            continue
        if ent["tier"] == "exact":
            leg.check(ent["series"] == true, "exact-count",
                      tenant=tenant, got=ent["series"], want=true)
        else:
            bound = max(err3 * true, 2)
            leg.check(abs(ent["series"] - true) <= bound, "hll-count",
                      tenant=tenant, got=ent["series"], want=true,
                      bound=round(bound, 1))
    att = info["tenants"].get("attacker", {})
    leg.stats["attacker_tier"] = att.get("tier")
    leg.stats["attacker_refused"] = att.get("refused")
    top_prefix = (att.get("top_prefixes") or [{}])[0].get("prefix")
    leg.check(top_prefix == "attack.flood", "heavy-hitter-prefix",
              got=top_prefix)

    # --- directory / per-metric hint index -------------------------------
    leg.stats["directory_series"] = tsdb.sketches.series_count()
    m0 = tsdb.metrics.get_id("attack.flood.m0")
    leg.stats["per_metric_index_m0"] = \
        tsdb.sketches.metric_series_count(m0)
    leg.check(leg.stats["per_metric_index_m0"]
              < leg.stats["directory_series"],
              "per-metric-index-partitions")

    # --- checkpoint: spill + snapshot + bloom pressure -------------------
    t_ck = time.time()
    tsdb.checkpoint()
    leg.stats["checkpoint_s"] = round(time.time() - t_ck, 2)
    stores = getattr(tsdb.store, "shards", None) or [tsdb.store]
    n_files = sum(len(s._ssts) for s in stores)
    leg.stats["sstable_files"] = n_files
    # Bloom under saturation: never a false negative for stored
    # series; measure the false-positive rate with absent hashes.
    probe_rng = np.random.default_rng(7)
    absent = probe_rng.integers(1 << 33, 1 << 34, size=2000)
    fp = total = 0
    for s in stores:
        for sst in s._ssts:
            for h in absent.tolist():
                total += 1
                if sst.bloom_may_contain_hash(tsdb.table,
                                              h & 0xFFFFFFFF):
                    fp += 1
    fpr = fp / total if total else 0.0
    leg.stats["bloom_fpr_absent"] = round(fpr, 4)
    # Theoretical (1 - e^{-kn/m})^k at this load, with headroom: the
    # point is measuring saturation honestly, not hiding it. (This
    # check caught the k=2 derivation whose second probe was a pure
    # function of the first mod the table size — 10x the envelope.)
    from opentsdb_tpu.storage.sstable import BLOOM_BITS, BLOOM_K
    per_table = S / max(len(stores), 1)
    expect = (1 - np.exp(-BLOOM_K * per_table
                         / BLOOM_BITS)) ** BLOOM_K
    leg.stats["bloom_fpr_expected"] = round(float(expect), 4)
    leg.check(fpr <= float(expect) * 2 + 0.01, "bloom-fpr",
              measured=round(fpr, 4), expected=round(float(expect), 4))

    # --- golden parity: one tag-filtered needle query --------------------
    ex = QueryExecutor(tsdb, backend="cpu")
    needle = S - 1 if S - 1 >= attacker_tried else 0
    spec = QuerySpec(f"hostile.card.m{needle % 8}",
                     {"id": str(needle)}, aggregator="sum")
    t_q = time.time()
    rs = ex.run(spec, T0 - 1, T0 + 30 * 3600)
    leg.stats["needle_query_ms"] = round(
        (time.time() - t_q) * 1000, 2)
    ok = (len(rs) == 1 and len(rs[0].values) == 1
          and float(rs[0].values[0]) == 1.0)
    leg.check(ok, "needle-query-parity",
              groups=len(rs),
              points=len(rs[0].values) if rs else 0)

    # --- snapshot round-trip ---------------------------------------------
    counts_before = {t: acct.count(t) for t in list(tried)}
    tsdb.shutdown()
    tsdb2 = open_writer(leg.dir, args.shards,
                        tenant_max_series=limit,
                        tenant_overrides=tuple(
                            f"{t}=0" for t in tenants))
    acct2 = tsdb2.tenants
    for tenant, before in counts_before.items():
        after = acct2.count(tenant)
        tier = acct2.snapshot_info()["tenants"][tenant]["tier"]
        if tier == "exact":
            leg.check(after == before, "reopen-exact-count",
                      tenant=tenant, got=after, want=before)
        else:
            bound = max(err3 * before, 2)
            leg.check(abs(after - before) <= bound,
                      "reopen-hll-count", tenant=tenant, got=after,
                      want=before)
    # The attacker stays refused across the reopen (limits are policy,
    # not memory): a NEW series must still refuse.
    try:
        tsdb2.add_batch("attack.flood.m0",
                        np.asarray([T0], np.int64), val,
                        {"id": "fresh-after-reopen"},
                        tenant="attacker")
        still_refused = False
    except TenantLimitError:
        still_refused = True
    leg.check(still_refused, "reopen-still-refuses",
              hint="--bug no-limit trips this too")
    tsdb2.shutdown()
    return leg.done()


# ---------------------------------------------------------------------------
# Leg: churn — series-churn cycles aging the fragment cache
# ---------------------------------------------------------------------------

def leg_churn(args, workdir: str) -> dict:
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec

    leg = Leg("churn", workdir)
    S = max(args.series // 50, 200)
    cycles = 2 if args.fast else 4
    log(f"[churn] {S} live series, {cycles} cycles")
    tsdb = open_writer(leg.dir, args.shards)
    ex = QueryExecutor(tsdb, backend="cpu")
    spec = QuerySpec("churn.m", {}, aggregator="sum",
                     downsample=(3600, "sum"))
    gen = 0
    live: list[int] = []
    cyc_stats = []
    for cyc in range(cycles):
        # Mint replacements for the churned half (gen increments keep
        # tag values fresh — new series, not re-puts).
        while len(live) < S:
            live.append(gen)
            gen += 1
        ts = T0 + np.arange(6, dtype=np.int64) * 3600 + cyc * 7
        for sid in live:
            tsdb.add_batch("churn.m", ts,
                           np.full(6, float(sid % 97)),
                           {"id": str(sid)}, tenant="churner")
        lo, hi = T0 - 1, T0 + 7 * 3600
        cold = ex.run(spec, lo, hi)
        t_w = time.time()
        warm = ex.run(spec, lo, hi)
        warm_ms = (time.time() - t_w) * 1000
        same = (len(cold) == len(warm)
                and all(np.array_equal(a.timestamps, b.timestamps)
                        and np.array_equal(a.values, b.values)
                        for a, b in zip(cold, warm)))
        leg.check(same, "warm-cold-parity", cycle=cyc)
        # Cold oracle: a FRESH executor shares no fragment cache state
        # with the aged one by key, so mismatches mean stale serving.
        fresh = QueryExecutor(tsdb, backend="cpu").run(spec, lo, hi)
        same = (len(fresh) == len(warm)
                and all(np.array_equal(a.values, b.values)
                        for a, b in zip(fresh, warm)))
        leg.check(same, "aged-vs-fresh-parity", cycle=cyc)
        # Churn: drop rows for half the live set, forget them.
        drop, live = live[:S // 2], live[S // 2:]
        for sid in drop:
            for h in range(6):
                key = tsdb.row_key_for("churn.m", {"id": str(sid)},
                                       T0 + h * 3600,
                                       create_metric=False,
                                       create_tags=False)
                tsdb.store.delete_row(tsdb.table, key)
        tsdb.checkpoint()
        cyc_stats.append({
            "cycle": cyc, "warm_ms": round(warm_ms, 2),
            "qcache_hits": ex.qcache_hits,
            "qcache_misses": ex.qcache_misses,
        })
    leg.stats["cycles"] = cyc_stats
    leg.stats["directory_series"] = tsdb.sketches.series_count()
    tsdb.shutdown()
    return leg.done()


# ---------------------------------------------------------------------------
# Leg: backfill — out-of-order storms racing rollup folds
# ---------------------------------------------------------------------------

def leg_backfill(args, workdir: str) -> dict:
    from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec

    leg = Leg("backfill", workdir)
    B = 32 if args.fast else 64
    rounds = 6 if args.fast else 12
    log(f"[backfill] {B} series, {rounds} storm rounds racing folds")
    tsdb = open_writer(leg.dir, args.shards, enable_rollups=True,
                       rollup_catchup="sync",
                       rollup_sketch_min_res=3600)
    rng = np.random.default_rng(args.seed + 1)
    fwd_hour = 0
    bwd_hour = 1
    n_points = 0
    t_ing = time.time()
    for r in range(rounds):
        # Forward stream: every series advances a fresh hour.
        ts = T0 + fwd_hour * 3600 + np.arange(12, dtype=np.int64) * 300
        fwd_hour += 1
        for s in range(B):
            tsdb.add_batch("bf.m", ts,
                           (ts % 89 + s).astype(np.float64),
                           {"id": str(s)}, tenant="bf")
            n_points += len(ts)
        # Backfill storm: late data into hours BELOW T0 (disjoint
        # range — re-ingest can't create conflicting duplicates),
        # racing the fold the checkpoint below runs.
        for _ in range(3):
            h = int(rng.integers(bwd_hour, bwd_hour + 8))
            ts_b = (T0 - (h + 1) * 3600
                    + np.arange(6, dtype=np.int64) * 600)
            s = int(rng.integers(0, B))
            tsdb.add_batch("bf.m", ts_b,
                           (ts_b % 83 + s).astype(np.float64),
                           {"id": str(s)}, tenant="bf")
            n_points += len(ts_b)
        bwd_hour += 8
        tsdb.checkpoint()   # fold races the storm deterministically
    leg.stats["points"] = n_points
    leg.stats["ingest_dps"] = round(
        n_points / (time.time() - t_ing), 1)
    tsdb.checkpoint()
    # Golden parity: rollup-served vs raw, bit-identical.
    ex = QueryExecutor(tsdb, backend="cpu")
    lo = T0 - (bwd_hour + 16) * 3600
    hi = T0 + (fwd_hour + 2) * 3600
    specs = [
        QuerySpec("bf.m", {}, aggregator="sum", downsample=(3600, "sum")),
        QuerySpec("bf.m", {}, aggregator="max", downsample=(86400, "max")),
        QuerySpec("bf.m", {}, aggregator="sum", downsample=(3600, "avg")),
        QuerySpec("bf.m", {}, aggregator="p95", downsample=(3600, "sum")),
        QuerySpec("bf.m", {"id": "3"}, aggregator="sum",
                  downsample=(3600, "sum")),
    ]
    rollup_served = 0
    for spec in specs:
        served, plan, _ = ex.run_with_plan(spec, lo, hi)
        saved, tsdb.rollups = tsdb.rollups, None
        try:
            raw = QueryExecutor(tsdb, backend="cpu").run(spec, lo, hi)
        finally:
            tsdb.rollups = saved
        if plan not in ("raw", "resident"):
            rollup_served += 1
        k_s = {tuple(sorted(r.tags.items())): r for r in served}
        k_r = {tuple(sorted(r.tags.items())): r for r in raw}
        # Single-series specs must be BIT-identical. Multi-series
        # merges interpolate across series at unaligned boundaries,
        # and the rollup path sums series in a different order than
        # the raw path — association-order ulp noise, so those get an
        # exact timestamp check plus a 1e-9 relative value bound
        # (far tighter than the repo's sketch parity tolerances).
        exact = bool(spec.tags)
        ok = set(k_s) == set(k_r) and all(
            np.array_equal(k_s[g].timestamps, k_r[g].timestamps)
            and (np.array_equal(k_s[g].values, k_r[g].values)
                 if exact else
                 np.allclose(k_s[g].values, k_r[g].values,
                             rtol=1e-9, atol=1e-9))
            for g in k_s)
        leg.check(ok, "rollup-vs-raw-parity",
                  agg=spec.aggregator, plan=plan, exact=exact)
    leg.stats["rollup_served_specs"] = rollup_served
    leg.check(rollup_served > 0, "rollup-actually-served")
    tsdb.shutdown()
    return leg.done()


# ---------------------------------------------------------------------------
# Leg: hot-tenant — one tenant saturating its owner replica via router
# ---------------------------------------------------------------------------

def leg_hot_tenant(args, workdir: str) -> dict:
    import zlib

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    import servematrix as sm

    leg = Leg("hot-tenant", workdir)
    n_q = 60 if args.fast else 200

    def owned_metric(owner: int) -> str:
        # The router's sub-query owner: crc32 of the full m-spec mod
        # backend count — pick a metric whose slot is replica-a.
        for i in range(2000):
            m = f"sum:hot.m{i}"
            if zlib.crc32(m.encode()) % 2 == owner:
                return m
        raise AssertionError("no owned metric found")

    m_hot = owned_metric(0)
    metric = m_hot.split(":", 1)[1]
    n_pts = 400
    # Real OS processes (the servematrix deployment): the delay
    # faultpoint armed on replica-a must NOT slow replica-b — an
    # in-process fleet shares one global faultpoint registry, which
    # silently turns "asymmetric load" into symmetric load.
    dep = sm.Deployment(
        leg.dir, seed=args.seed,
        router_args=["--router-hedge-ms", "25",
                     "--query-rate", "8", "--query-burst", "4"])
    try:
        dep.start()
        lines = ["tenant hot"] + [
            f"put {metric} {T0 + i * 60} {i % 13} host=a"
            for i in range(n_pts)]
        sm.telnet_acked(dep.ports["writer"], lines)
        target = (f"/q?start={T0 - 1}&end={T0 + n_pts * 60}"
                  f"&m={m_hot}&json&nocache=1")

        def wait_serving(port: int, timeout: float = 30.0) -> int:
            deadline = time.time() + timeout
            got = -1
            while time.time() < deadline:
                try:
                    st, _, body = sm.http_get(port, target, timeout=10)
                    if st == 200:
                        got = sum(len(r["dps"])
                                  for r in json.loads(body))
                        if got >= n_pts:
                            return got
                except Exception:
                    pass
                time.sleep(0.2)
            return got

        for name in ("replica-a", "replica-b"):
            got = wait_serving(dep.ports[name])
            leg.check(got == n_pts, "replica-caught-up", replica=name,
                      got=got, want=n_pts)
        # Golden answer: the writer's own /q (no router in the path).
        st, _, body = sm.http_get(dep.ports["writer"], target)
        assert st == 200, f"writer direct query failed: {st}"
        want = {r["metric"]: r["dps"] for r in json.loads(body)}

        def router_q(tenant: str):
            return sm.http_get(dep.ports["router"],
                               target + f"&tenant={tenant}",
                               timeout=30)

        # Warmup (no fault): replica-a is the owner and fast, so it
        # wins its own hops and seeds its hop-latency histogram —
        # the baseline the p95 attribution check compares against.
        for _ in range(6):
            st, _, body = router_q("warm")
            leg.check(st == 200, "warmup-served", status=st)
            time.sleep(0.15)   # under the 8/s tenant quota

        # --- asymmetric load: slow ONLY the owner replica ----------------
        st, _, _ = sm.http_get(
            dep.ports["replica-a"],
            "/fault?arm=query.scan%3Ddelay%3Adelay%3D0.12"
            "%3Acount%3D100000")
        assert st == 200, "arming the delay faultpoint failed"
        served = shed = undeclared = parity_bad = 0
        for i in range(n_q):
            st, hdrs, body = router_q("hot")
            if st == 200:
                served += 1
                got = {r["metric"]: r["dps"]
                       for r in json.loads(body)}
                if got != want:
                    parity_bad += 1
            elif st == 429:
                shed += 1
                if "Retry-After" not in hdrs:
                    undeclared += 1
            else:
                undeclared += 1
            time.sleep(0.01)
        st, _, body = sm.http_get(dep.ports["router"], "/api/topology")
        topo = json.loads(body)
        counters = topo.get("counters", {})
        reps = {r["url"].rsplit(":", 1)[1]: r
                for r in topo.get("replicas", [])}
        rep_a = reps.get(str(dep.ports["replica-a"]), {})
        rep_b = reps.get(str(dep.ports["replica-b"]), {})
        leg.stats.update(served=served, shed=shed,
                         undeclared=undeclared, parity_bad=parity_bad,
                         hedges=counters.get("hedges"),
                         hedge_wins=counters.get("hedge_wins"))
        leg.stats["hop_p95_ms"] = {
            "replica-a": rep_a.get("hop_p95_ms"),
            "replica-b": rep_b.get("hop_p95_ms")}
        leg.check(served > 0, "some-queries-served")
        leg.check(shed > 0, "quota-actually-shed",
                  hint="per-tenant query bucket never fired")
        leg.check(undeclared == 0, "undeclared-shed-or-error",
                  count=undeclared)
        leg.check(parity_bad == 0, "router-answer-parity",
                  bad=parity_bad)
        leg.check((counters.get("hedges") or 0) > 0, "hedges-fired")
        leg.check((counters.get("hedge_wins") or 0) > 0, "hedges-won",
                  hint="the fast replica should win hedged "
                       "duplicates")
        # p95 attribution: BOTH replicas carry a measured hop p95 in
        # /api/topology (the owner from its warmup wins, the fast
        # replica from its hedge wins) — the dashboard can name which
        # replica is slow without scraping logs.
        leg.check(rep_a.get("hop_p95_ms") is not None
                  and rep_b.get("hop_p95_ms") is not None,
                  "topology-p95-attribution",
                  got=leg.stats["hop_p95_ms"])

        # --- ejection + readmission under hard failure -------------------
        # Escalate the slow replica to errors: hops to it now 500,
        # the router must eject it after consecutive failures — and
        # the health probe (its /healthz still answers) must readmit
        # it once the fault clears.
        sm.http_get(dep.ports["replica-a"],
                    "/fault?arm=query.scan%3Dioerror%3Acount%3D100000")
        ejected = False
        deadline = time.time() + 30
        while time.time() < deadline and not ejected:
            st, _, body = router_q("ejector")
            leg.check(st in (200, 429), "served-during-ejection",
                      status=st)
            st, _, body = sm.http_get(dep.ports["router"],
                                      "/api/topology")
            topo = json.loads(body)
            ejected = (topo["counters"].get("ejections", 0) > 0)
            time.sleep(0.1)
        leg.check(ejected, "slow-replica-ejects")
        sm.http_get(dep.ports["replica-a"], "/fault?clear=1")
        readmitted = False
        deadline = time.time() + 30
        while time.time() < deadline and not readmitted:
            st, _, body = sm.http_get(dep.ports["router"],
                                      "/api/topology")
            topo = json.loads(body)
            rep_a = [r for r in topo["replicas"]
                     if r["url"].endswith(str(dep.ports["replica-a"]))]
            readmitted = (topo["counters"].get("readmissions", 0) > 0
                          and rep_a and rep_a[0]["healthy"])
            time.sleep(0.1)
        leg.check(readmitted, "ejected-replica-readmits")
        leg.stats["ejections"] = topo["counters"].get("ejections")
        leg.stats["readmissions"] = topo["counters"].get(
            "readmissions")
        # Post-readmit sanity: the fleet serves the golden answer.
        st, _, body = router_q("after")
        got = ({r["metric"]: r["dps"] for r in json.loads(body)}
               if st == 200 else None)
        leg.check(st == 200 and got == want, "post-readmit-parity",
                  status=st)
    finally:
        dep.stop()
    return leg.done()


# ---------------------------------------------------------------------------

LEGS = {
    "cardinality": leg_cardinality,
    "churn": leg_churn,
    "backfill": leg_backfill,
    "hot-tenant": leg_hot_tenant,
}


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"comma-separated subset of: {','.join(LEGS)}")
    ap.add_argument("--series", type=int, default=None,
                    help="distinct series for the cardinality leg "
                         "(default 1000000; --fast default 20000)")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized legs (the tier-1 subset)")
    ap.add_argument("--bug", default=None, choices=["no-limit"],
                    help="sabotage: disable the series limiter; the "
                         "harness MUST flag the missing refusals "
                         "(the gate)")
    ap.add_argument("--json", default="BENCH_HOSTILE.json")
    ap.add_argument("--work-dir", default=None)
    args = ap.parse_args()
    if args.series is None:
        args.series = 20_000 if args.fast else 1_000_000
    if args.bug:
        os.environ["TSDB_TENANT_BUG"] = args.bug
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    work = args.work_dir or tempfile.mkdtemp(prefix="hostile-")
    os.makedirs(work, exist_ok=True)

    legs = []
    t0 = time.time()
    for name in args.legs.split(","):
        name = name.strip()
        if not name:
            continue
        if name not in LEGS:
            log(f"unknown leg {name!r} (one of {', '.join(LEGS)})")
            return 2
        legs.append(LEGS[name](args, work))
    total_checks = sum(x["checks"] for x in legs)
    total_viol = sum(len(x["violations"]) for x in legs)
    artifact = {
        "bug": args.bug,
        "fast": bool(args.fast),
        "series": args.series,
        "shards": args.shards,
        "seed": args.seed,
        "wall_s": round(time.time() - t0, 2),
        "checks": total_checks,
        "violations": total_viol,
        "legs": legs,
    }
    with open(args.json, "w") as f:
        json.dump(artifact, f, indent=1)
    log(f"checks={total_checks} violations={total_viol} "
        f"-> {args.json}")
    if args.bug:
        if total_viol == 0:
            log("GATE FAILED: sabotage was NOT flagged — the harness "
                "cannot catch a disabled limiter")
            return 1
        log(f"gate ok: {total_viol} violations flagged under --bug")
        return 0
    return 0 if total_viol == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
