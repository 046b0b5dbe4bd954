"""Expert parallelism: route aggregator families to device groups.

The reference has no MoE-style structure (SURVEY.md §2.9); its nearest
behavior is that a mixed dashboard request (`/q` with several `m=` specs,
reference src/tsd/GraphHandler.java:155-187) runs each sub-query's
aggregator sequentially on one CPU thread. The TPU-native analog planned
in SURVEY §2.9 is genuine expert parallelism: when one batch of queries
mixes aggregator *families* — moment reductions (sum/min/max/avg/dev/
count), t-digest percentiles, HLL cardinality — partition the mesh into
device groups, one per family, and run every family concurrently under a
single jit. Each chip traces all three family kernels but executes only
its own (``lax.switch`` on the device's routed family id), so a mixed
batch costs max(family) wall-clock instead of sum(family).

Shapes are the usual EP trade: all families share one padded slot layout
([D, Q, N] point arrays, [D, Q, OUT] results) so the routed computation
stays static-shaped for XLA.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from opentsdb_tpu.ops import sketches
from opentsdb_tpu.ops.kernels import (
    _finish,
    _segment_moments,
    downsample_group,
    gap_fill,
    group_moments,
    masked_quantile_axis0,
)
from opentsdb_tpu.parallel.compile import compile_with_plan
from opentsdb_tpu.parallel.mesh import EXPERT_AXIS
from opentsdb_tpu.parallel.plan import ExecPlan

FAMILIES = ("moment", "percentile", "cardinality")
FAMILY_ID = {name: i for i, name in enumerate(FAMILIES)}


class MomentSpec(NamedTuple):
    """Static params shared by the moment-family queries in a batch."""
    num_series: int
    num_buckets: int
    interval: int
    agg_down: str = "avg"
    agg_group: str = "sum"


class PercentileSpec(NamedTuple):
    qs: tuple = (0.5, 0.95, 0.99)
    compression: int = sketches.DEFAULT_COMPRESSION


class CardinalitySpec(NamedTuple):
    p: int = sketches.DEFAULT_HLL_P


class ExpertSpecs(NamedTuple):
    moment: MomentSpec
    percentile: PercentileSpec = PercentileSpec()
    cardinality: CardinalitySpec = CardinalitySpec()

    def out_len(self) -> int:
        return max(self.moment.num_buckets, len(self.percentile.qs), 1)


class ExpertPlan(NamedTuple):
    """Host-side routing: which (device, slot) runs which query."""
    fam: np.ndarray          # [D] int32 family id per device
    ts: np.ndarray           # [D, Q, N] int32
    vals: np.ndarray         # [D, Q, N] float32
    items: np.ndarray        # [D, Q, N] int32 (cardinality hash inputs)
    sid: np.ndarray          # [D, Q, N] int32
    valid: np.ndarray        # [D, Q, N] bool
    slot_of: list            # query index -> (device, slot)


def plan_expert_batch(queries: Sequence[dict], n_devices: int) -> ExpertPlan:
    """Route a mixed query batch onto device groups by aggregator family.

    Each query dict: {"family": str, "ts": [n], "vals": [n], "sid": [n]}
    (moment) or {"family": "percentile"|"cardinality", "vals"|"items": [n]}.
    Devices are split proportionally to each present family's query count
    (every present family gets at least one device); queries round-robin
    within their family's group.
    """
    for qi, q in enumerate(queries):
        if q["family"] not in FAMILY_ID:
            raise ValueError(
                f"query {qi}: unknown family {q['family']!r} "
                f"(expected one of {FAMILIES})")
    present = [f for f in FAMILIES if any(q["family"] == f for q in queries)]
    if not present:
        raise ValueError("empty query batch")
    if n_devices < len(present):
        raise ValueError(
            f"{len(present)} families need >= that many devices, "
            f"have {n_devices}")
    counts = {f: sum(q["family"] == f for q in queries) for f in present}
    total = sum(counts.values())
    # Proportional split, >=1 each, remainder to the largest families.
    alloc = {f: max(1, n_devices * counts[f] // total) for f in present}
    while sum(alloc.values()) > n_devices:
        alloc[max(alloc, key=lambda f: alloc[f])] -= 1
    while sum(alloc.values()) < n_devices:
        alloc[max(present, key=lambda f: counts[f] / alloc[f])] += 1

    dev_fam = []
    group_devs: dict[str, list[int]] = {}
    for f in present:
        group_devs[f] = list(range(len(dev_fam), len(dev_fam) + alloc[f]))
        dev_fam += [FAMILY_ID[f]] * alloc[f]

    slots: list[list[int]] = [[] for _ in range(n_devices)]
    slot_of: list[tuple[int, int]] = []
    rr = {f: 0 for f in present}
    for qi, q in enumerate(queries):
        devs = group_devs[q["family"]]
        d = devs[rr[q["family"]] % len(devs)]
        rr[q["family"]] += 1
        slot_of.append((d, len(slots[d])))
        slots[d].append(qi)

    q_max = max(len(s) for s in slots)
    n_max = max(
        (len(np.atleast_1d(q.get("vals", q.get("items", [0.0])))) for q in
         queries), default=1)
    n_max = max(n_max, 1)
    shape = (n_devices, q_max, n_max)
    ts = np.zeros(shape, np.int32)
    vals = np.zeros(shape, np.float32)
    items = np.zeros(shape, np.int32)
    sid = np.zeros(shape, np.int32)
    valid = np.zeros(shape, bool)
    for d, devq in enumerate(slots):
        for s, qi in enumerate(devq):
            q = queries[qi]
            if q["family"] == "cardinality":
                arr = np.asarray(q["items"])
                items[d, s, :len(arr)] = arr
                n = len(arr)
            else:
                v = np.asarray(q["vals"], np.float32)
                vals[d, s, :len(v)] = v
                n = len(v)
                if q["family"] == "moment":
                    t = np.asarray(q["ts"], np.int32)
                    ts[d, s, :len(t)] = t
                    sid[d, s, :len(t)] = np.asarray(q["sid"], np.int32)
            valid[d, s, :n] = True
    return ExpertPlan(np.asarray(dev_fam, np.int32), ts, vals, items, sid,
                      valid, slot_of)


def _expert_query_body(fam, ts, vals, items, sid, valid, *,
                       specs: ExpertSpecs):
    out = specs.out_len()
    mspec, pspec, cspec = specs.moment, specs.percentile, specs.cardinality
    qs = jnp.asarray(pspec.qs, jnp.float32)

    def pad_to(v, m):
        return (jnp.pad(v, ((0, 0), (0, out - v.shape[1]))),
                jnp.pad(m, ((0, 0), (0, out - m.shape[1]))))

    def run_moment(ts, vals, items, sid, valid):
        def one(args):
            t, v, s, m = args
            r = downsample_group(
                t, v, s, m, num_series=mspec.num_series,
                num_buckets=mspec.num_buckets, interval=mspec.interval,
                agg_down=mspec.agg_down, agg_group=mspec.agg_group)
            return r["group_values"], r["group_mask"]
        gv, gm = jax.lax.map(one, (ts, vals, sid, valid))
        return pad_to(gv, gm)

    def run_percentile(ts, vals, items, sid, valid):
        def one(args):
            _, v, _, m = args
            means, weights = sketches.tdigest_init(pspec.compression)
            means, weights = sketches.tdigest_add(
                means, weights, v, m, compression=pspec.compression)
            return sketches.tdigest_quantile(means, weights, qs)
        qv = jax.lax.map(one, (ts, vals, sid, valid))
        return pad_to(qv, jnp.ones_like(qv, bool))

    def run_cardinality(ts, vals, items, sid, valid):
        def one(args):
            t, _, it, m = args
            regs = sketches.hll_init(cspec.p)
            regs = sketches.hll_add(regs, it, m, p=cspec.p)
            return sketches.hll_estimate(regs)[None]
        cv = jax.lax.map(
            one, (ts, vals, items, valid))
        return pad_to(cv, jnp.ones_like(cv, bool))

    my_fam = fam[0]
    v, m = jax.lax.switch(
        my_fam,
        [run_moment, run_percentile, run_cardinality],
        ts[0], vals[0], items[0], sid[0], valid[0])
    return v[None], m[None]


EXPERT_QUERY_PLAN = ExecPlan(
    name="expert.query_step", axis="expert", style="shard_map",
    in_specs=(P(EXPERT_AXIS),) * 6,
    out_specs=(P(EXPERT_AXIS), P(EXPERT_AXIS)))


def expert_query_step(fam, ts, vals, items, sid, valid, *, mesh,
                      specs: ExpertSpecs):
    """One mixed-family batch over the mesh's expert axis.

    fam [D]; point arrays [D, Q, N]. Returns (values [D, Q, OUT],
    mask [D, Q, OUT]) — device d's rows hold that device's routed
    queries, trimmed by the mask.
    """
    fn = compile_with_plan(_expert_query_body, EXPERT_QUERY_PLAN, mesh,
                           statics=(("specs", specs),))
    return fn(fam, ts, vals, items, sid, valid)


def run_mixed_batch(queries: Sequence[dict], mesh, specs: ExpertSpecs):
    """Plan, execute, and unpack a mixed aggregator batch.

    Returns one numpy array per query: moment queries get their [B] group
    values (masked entries NaN), percentile queries their quantiles,
    cardinality queries a scalar estimate.
    """
    plan = plan_expert_batch(queries, n_devices=mesh.devices.size)
    values, mask = expert_query_step(
        plan.fam, plan.ts, plan.vals, plan.items, plan.sid, plan.valid,
        mesh=mesh, specs=specs)
    values = np.asarray(values)
    mask = np.asarray(mask)
    results = []
    for qi, q in enumerate(queries):
        d, s = plan.slot_of[qi]
        row, rm = values[d, s], mask[d, s]
        if q["family"] == "moment":
            out = np.where(rm[:specs.moment.num_buckets],
                           row[:specs.moment.num_buckets], np.nan)
        elif q["family"] == "percentile":
            out = row[:len(specs.percentile.qs)]
        else:
            out = row[0]
        results.append(out)
    return results


# ---------------------------------------------------------------------------
# Expert-parallel DASHBOARD batches (the /q serving face)
# ---------------------------------------------------------------------------
#
# The legacy expert_query_step above is the research kernel (its own
# family specs, t-digest percentiles). Dashboard serving needs exact
# /q semantics: each sub-query's answer must match the serial leg's
# fused downsample+group kernel (ops/kernels.downsample_group and the
# percentile branch of the executor) to f32 tolerance. So the dash
# families are (moment, percentile) with the SERIAL kernels' exact op
# sequence per slot — the downsample aggregator and the group
# aggregator are per-slot TRACED switch indices (computing every
# segment statistic and selecting is bitwise-identical to the gated
# serial form, each statistic being an independent segment reduction),
# so one compile serves a whole dashboard of mixed sum/avg/max/pNN
# panels and slots pack by family instead of serializing.

DASH_FAMILIES = ("moment", "percentile")
DASH_AGGS = ("sum", "min", "max", "avg", "dev", "count")
DASH_AGG_ID = {name: i for i, name in enumerate(DASH_AGGS)}


def _finish_switch(agg_id, stats):
    """_finish with a traced aggregator: every statistic is already
    computed, so every finishing arithmetic is evaluated too
    (elementwise, small next to the reductions) and the traced id
    picks one — the same bits as the gated form. Deliberately not a
    ``lax.switch``: XLA:TPU (libtpu 0.0.34, v5e) dies with SIGILL
    compiling the group stage's switch over these branches (PR 21)."""
    return jnp.stack([_finish(a, *stats) for a in DASH_AGGS])[agg_id]


class DashPlan(NamedTuple):
    """Host-side routing of one dashboard batch (the plan_expert_batch
    shape plus per-slot traced aggregator ids and quantiles)."""
    fam: np.ndarray        # [D] int32 family id per device
    ts: np.ndarray         # [D, Q, N] int32 rel offsets
    vals: np.ndarray       # [D, Q, N] float32
    sid: np.ndarray        # [D, Q, N] int32
    valid: np.ndarray      # [D, Q, N] bool
    ds_id: np.ndarray      # [D, Q] int32 downsample-agg switch index
    agg_id: np.ndarray     # [D, Q] int32 group-agg switch index
    q: np.ndarray          # [D, Q] float32 quantile (percentile slots)
    slot_of: list          # query index -> (device, slot)


def plan_dashboard_batch(queries: Sequence[dict],
                         n_devices: int) -> DashPlan:
    """Route dashboard sub-queries onto device groups by family.

    Each query dict: {"family": "moment"|"percentile", "ts": [n] rel
    offsets, "vals": [n], "sid": [n], "dsagg": str, "agg": str} plus
    "quantile" for percentile slots. Devices split proportionally to
    family query counts (each present family gets >= 1); queries
    round-robin within their family's group.
    """
    fam_id = {name: i for i, name in enumerate(DASH_FAMILIES)}
    for qi, qq in enumerate(queries):
        if qq["family"] not in fam_id:
            raise ValueError(f"query {qi}: unknown dash family "
                             f"{qq['family']!r}")
    present = [f for f in DASH_FAMILIES
               if any(qq["family"] == f for qq in queries)]
    if not present:
        raise ValueError("empty dashboard batch")
    if n_devices < len(present):
        raise ValueError(f"{len(present)} families need >= that many "
                         f"devices, have {n_devices}")
    counts = {f: sum(qq["family"] == f for qq in queries)
              for f in present}
    total = sum(counts.values())
    alloc = {f: max(1, n_devices * counts[f] // total) for f in present}
    while sum(alloc.values()) > n_devices:
        alloc[max(alloc, key=lambda f: alloc[f])] -= 1
    while sum(alloc.values()) < n_devices:
        alloc[max(present, key=lambda f: counts[f] / alloc[f])] += 1

    dev_fam = []
    group_devs: dict[str, list[int]] = {}
    for f in present:
        group_devs[f] = list(range(len(dev_fam), len(dev_fam) + alloc[f]))
        dev_fam += [fam_id[f]] * alloc[f]

    slots: list[list[int]] = [[] for _ in range(n_devices)]
    slot_of: list[tuple[int, int]] = []
    rr = {f: 0 for f in present}
    for qi, qq in enumerate(queries):
        devs = group_devs[qq["family"]]
        d = devs[rr[qq["family"]] % len(devs)]
        rr[qq["family"]] += 1
        slot_of.append((d, len(slots[d])))
        slots[d].append(qi)

    q_max = max(len(sl) for sl in slots)
    n_max = max((len(np.atleast_1d(qq["vals"])) for qq in queries),
                default=1)
    n_max = max(n_max, 1)
    shape = (n_devices, q_max, n_max)
    ts = np.zeros(shape, np.int32)
    vals = np.zeros(shape, np.float32)
    sid = np.zeros(shape, np.int32)
    valid = np.zeros(shape, bool)
    ds_id = np.zeros((n_devices, q_max), np.int32)
    agg_id = np.zeros((n_devices, q_max), np.int32)
    qarr = np.zeros((n_devices, q_max), np.float32)
    for d, devq in enumerate(slots):
        for sl, qi in enumerate(devq):
            qq = queries[qi]
            n = len(qq["vals"])
            ts[d, sl, :n] = np.asarray(qq["ts"], np.int32)
            vals[d, sl, :n] = np.asarray(qq["vals"], np.float32)
            sid[d, sl, :n] = np.asarray(qq["sid"], np.int32)
            valid[d, sl, :n] = True
            ds_id[d, sl] = DASH_AGG_ID[qq["dsagg"]]
            if qq["family"] == "moment":
                agg_id[d, sl] = DASH_AGG_ID[qq["agg"]]
            else:
                qarr[d, sl] = float(qq["quantile"])
    return DashPlan(np.asarray(dev_fam, np.int32), ts, vals, sid,
                    valid, ds_id, agg_id, qarr, slot_of)


def _dash_series_stage(t, v, s, m, ds_id, *, num_series, num_buckets,
                       interval):
    """The serial kernels' series stage with a traced downsampler: one
    fused segment reduction producing [S, B] grids (the op sequence of
    ops.kernels._series_stage, every statistic materialized so the
    per-slot switch can pick)."""
    bucket = jnp.clip(t // interval, 0, num_buckets - 1)
    nseg = num_series * num_buckets + 1
    seg = jnp.where(m, s * num_buckets + bucket, nseg - 1)
    count, total, m2, mn, mx = _segment_moments(v, seg, m, nseg)
    per = _finish_switch(ds_id, (count, total, m2, mn, mx))
    shape = (num_series, num_buckets)
    return per[:-1].reshape(shape), count[:-1].reshape(shape) > 0


def _expert_dash_body(fam, ts, vals, sid, valid, ds_id, agg_id, q, *,
                      num_series, num_buckets, interval):
    """Per-device body: run this device's routed slots under its
    family's kernel (lax.switch on the routed family id; every device
    traces both, executes one)."""
    my_fam = fam[0]
    ts, vals, sid, valid = ts[0], vals[0], sid[0], valid[0]
    ds_id, agg_id, q = ds_id[0], agg_id[0], q[0]

    def moment_slot(args):
        t, v, s, m, di, ai, _ = args
        sv, sm = _dash_series_stage(
            t, v, s, m, di, num_series=num_series,
            num_buckets=num_buckets, interval=interval)
        filled, in_range = gap_fill(sv, sm, num_buckets)
        g_n, g_total, g_m2, _, g_mn, g_mx = group_moments(filled,
                                                          in_range)
        gv = _finish_switch(ai, (g_n, g_total, g_m2, g_mn, g_mx))
        return gv, sm.any(axis=0)

    def pct_slot(args):
        t, v, s, m, di, _, qq = args
        sv, sm = _dash_series_stage(
            t, v, s, m, di, num_series=num_series,
            num_buckets=num_buckets, interval=interval)
        filled, in_range = gap_fill(sv, sm, num_buckets)
        gv = masked_quantile_axis0(filled, in_range, qq[None])[0]
        return gv, sm.any(axis=0)

    operands = (ts, vals, sid, valid, ds_id, agg_id, q)

    def run_moment(ops):
        return jax.lax.map(moment_slot, ops)

    def run_pct(ops):
        return jax.lax.map(pct_slot, ops)

    gv, gm = jax.lax.switch(my_fam, [run_moment, run_pct], operands)
    return gv[None], gm[None]


EXPERT_DASH_PLAN = ExecPlan(
    name="expert.dashboard_step", axis="expert", style="shard_map",
    in_specs=(P(EXPERT_AXIS),) * 8,
    out_specs=(P(EXPERT_AXIS), P(EXPERT_AXIS)))


def run_dashboard_batch(queries: Sequence[dict], mesh, *,
                        num_series: int, num_buckets: int,
                        interval: int):
    """Plan, execute and unpack one mixed dashboard batch over the
    mesh's expert axis. Returns [(values [B] f32, mask [B] bool)] per
    query, semantics matching the serial fused kernels (f32 tolerance
    — group sums reduce in a different padding order)."""
    from opentsdb_tpu.parallel.plan import flatten_series_mesh
    devs = flatten_series_mesh(mesh).devices.reshape(-1)
    from jax.sharding import Mesh
    emesh = Mesh(devs, (EXPERT_AXIS,))
    plan = plan_dashboard_batch(queries, n_devices=devs.size)
    fn = compile_with_plan(
        _expert_dash_body, EXPERT_DASH_PLAN, emesh,
        statics=(("num_series", num_series),
                 ("num_buckets", num_buckets),
                 ("interval", interval)))
    values, mask = fn(plan.fam, plan.ts, plan.vals, plan.sid,
                      plan.valid, plan.ds_id, plan.agg_id, plan.q)
    values = np.asarray(values)
    mask = np.asarray(mask)
    out = []
    for qi in range(len(queries)):
        d, sl = plan.slot_of[qi]
        out.append((values[d, sl], mask[d, sl]))
    return out
