"""Jitted JAX kernels for the query/compaction compute path.

Design rules (SURVEY.md §7.4):
- Fixed shapes everywhere: callers pad to static sizes and pass masks or
  counts. No data-dependent Python control flow; everything lowers to one
  XLA computation per (shape, static-arg) combination.
- The primary layout is FLAT: all points of all series in a query live in
  one [N] array with a parallel [N] series-id array, so ragged series waste
  no compute. Downsample + group-by is then a pair of segment reductions
  (points -> series x bucket -> bucket) — this replaces the reference's
  k-way merge iterator stack (SpanGroup.SGIterator,
  Span.DownsamplingIterator). The second is dense [S, B] work on the VPU.
  The first is a scatter, and XLA:TPU applies a scatter's updates one
  after another: 8.9 ns an update on a v5e, four orders of magnitude
  under the HBM roofline, and all the device did in the resident cells
  until PR 39 (PERF.md §5-§6). So the resident fold (window.chunk_fold)
  reduces each run of equal segment ids on the VPU first and scatters
  one update a run (_scatter_runs), and since PR 46 so does the stage
  of the plans that read past the horizon (_series_stage, through
  _run_moments); _segment_moments, a slot an update, is what the mesh
  legs still take (parallel/).
- Timestamps enter as int32 *offsets from the query start*; values as
  float32. Bucket mean-timestamps are computed relative to each bucket
  start so float32 stays exact (offsets < interval <= 2^24).

Aggregator semantics match ops/oracle.py (the numpy float64 oracle); golden
tests compare the two.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.core.const import NOLERP_AGGS
from opentsdb_tpu.parallel.compile import compile_with_plan, jit_plan
from opentsdb_tpu.parallel.plan import ExecPlan

# Execution plans (parallel/plan.py): every jitted kernel in this
# module compiles through the mesh execution plane. With no mesh (the
# plane's default) each plan is exactly the per-site jax.jit it
# replaced — same statics, same donation, bit-identical programs; the
# plane is where the batch axis each kernel shards over is DECLARED
# (series-hash for the window/downsample family) so mesh legs
# (parallel/sharded.py, compress/) stay partition-aware without
# per-site plumbing.
_RATE_STATICS = ("rate", "counter", "drop_resets")

# Plain Python floats: creating jnp scalars at import time would
# instantiate a device array and eagerly initialize the backend.
_NEG_INF = float("-inf")
_POS_INF = float("inf")


# ---------------------------------------------------------------------------
# Masked segment reductions
# ---------------------------------------------------------------------------

# Which per-segment statistics each aggregator's _finish needs. count is
# always computed (it doubles as the bucket-nonempty mask); the rest are
# gated off it so e.g. a sum query scatters 2 statistics, not 5: a
# turn of _scatter_runs reduces and scatters each needed statistic
# of a block's runs and nothing else (_segment_moments: one flat rank-1
# reduction of the [N] slots a needed statistic; relative cost of
# stacked [N, K] scatters: not measured on a local chip).
_AGG_NEEDS = {"sum": frozenset({"sum"}), "min": frozenset({"min"}),
              "max": frozenset({"max"}), "avg": frozenset({"sum"}),
              "dev": frozenset({"sum", "m2"}),
              "count": frozenset()}


def _needs(agg: str) -> frozenset:
    return _AGG_NEEDS[NOLERP_AGGS.get(agg, agg)]


def _segment_moments(vals: jnp.ndarray, seg: jnp.ndarray, valid: jnp.ndarray,
                     num_segments: int,
                     need: frozenset = frozenset({"sum", "m2", "min",
                                                  "max"})):
    """Per-segment count, sum, centered-M2, min, max over masked points.

    The second moment is centered (two-pass: mean first, then
    sum((x-mean)^2)) — the naive E[x^2]-E[x]^2 form cancels catastrophically
    in float32 when stddev << |mean|.

    ``need`` gates which statistics are materialized (see _AGG_NEEDS);
    un-needed ones return None.
    """
    count = jax.ops.segment_sum(valid.astype(jnp.float32), seg,
                                num_segments)
    total = m2 = mn = mx = None
    if "sum" in need or "m2" in need:
        total = jax.ops.segment_sum(jnp.where(valid, vals, 0.0), seg,
                                    num_segments)
    if "m2" in need:
        mean = total / jnp.maximum(count, 1.0)
        centered = jnp.where(valid, vals - mean[seg], 0.0)
        m2 = jax.ops.segment_sum(centered * centered, seg, num_segments)
    if "min" in need:
        mn = jax.ops.segment_min(jnp.where(valid, vals, _POS_INF), seg,
                                 num_segments)
    if "max" in need:
        mx = jax.ops.segment_max(jnp.where(valid, vals, _NEG_INF), seg,
                                 num_segments)
    return count, total, m2, mn, mx


def _finish(agg: str, count, total, m2, mn, mx):
    """Combine segment moments (m2 = centered sum of squares) into the agg."""
    agg = NOLERP_AGGS.get(agg, agg)  # same reduction, different feed
    safe = jnp.maximum(count, 1.0)
    if agg == "sum":
        return total
    if agg == "min":
        return mn
    if agg == "max":
        return mx
    if agg == "avg":
        return total / safe
    if agg == "dev":
        return jnp.sqrt(jnp.maximum(m2, 0.0) / safe)
    if agg == "count":
        return count
    raise ValueError(f"unknown aggregator: {agg}")


_I32_BIG = int(np.int32(2**31 - 1))


def gap_fill(series_values: jnp.ndarray, series_mask: jnp.ndarray,
             num_buckets: int, *, glob_offset=0, left_idx=None,
             left_val=None, right_idx=None, right_val=None):
    """Lerp-fill each series' empty buckets between its nonempty ones.

    A series with an empty bucket between two nonempty ones contributes a
    linear interpolation (the reference lerps missing samples at group
    time, SpanGroup.java:702-784); outside its first/last nonempty bucket
    it contributes nothing. Fill via cumulative min/max index scans — no
    sort, no gather loops. Bucket starts are affine in the bucket index,
    so lerping in index space equals lerping in time space.

    The optional carry args serve the time-sharded path
    (parallel/timeshard.py), where this tile's buckets are a window
    ``[glob_offset, glob_offset + num_buckets)`` of a larger grid:
    ``left_idx/left_val`` [S] give the nearest nonempty *global* bucket
    before the window (-1 = none), ``right_idx/right_val`` the nearest
    after (sentinel 2^31-1 = none); rows with no local prev/next fall
    back to them so cross-tile lerp matches the unsharded fill exactly.

    Returns (filled [S, B], in_range [S, B]); filled is 0 outside range.
    """
    b_idx = jnp.arange(num_buckets, dtype=jnp.int32)
    glob = glob_offset + b_idx
    prev_loc = jax.lax.cummax(
        jnp.where(series_mask, b_idx[None, :], -1), axis=1)
    next_loc = jax.lax.cummin(
        jnp.where(series_mask, b_idx[None, :], num_buckets), axis=1,
        reverse=True)
    has_prev_loc = prev_loc >= 0
    has_next_loc = next_loc < num_buckets
    p = jnp.clip(prev_loc, 0, num_buckets - 1)
    q = jnp.clip(next_loc, 0, num_buckets - 1)
    y0 = jnp.take_along_axis(series_values, p, axis=1)
    y1 = jnp.take_along_axis(series_values, q, axis=1)

    if left_idx is None:
        prev_idx = jnp.where(has_prev_loc, glob_offset + prev_loc, -1)
        prev_val = y0
    else:
        prev_idx = jnp.where(has_prev_loc, glob_offset + prev_loc,
                             left_idx[:, None])
        prev_val = jnp.where(has_prev_loc, y0, left_val[:, None])
    if right_idx is None:
        next_idx = jnp.where(has_next_loc, glob_offset + next_loc, _I32_BIG)
        next_val = y1
    else:
        next_idx = jnp.where(has_next_loc, glob_offset + next_loc,
                             right_idx[:, None])
        next_val = jnp.where(has_next_loc, y1, right_val[:, None])

    in_range = (prev_idx >= 0) & (next_idx < _I32_BIG)
    dx = jnp.maximum((next_idx - prev_idx).astype(jnp.float32), 1.0)
    frac = (glob[None, :] - prev_idx).astype(jnp.float32) / dx
    filled = jnp.where(series_mask, series_values,
                       prev_val + frac * (next_val - prev_val))
    return jnp.where(in_range, filled, 0.0), in_range


def bucket_rate(series_values: jnp.ndarray, series_mask: jnp.ndarray,
                interval: int, counter_max=0.0, reset_value=0.0, *,
                counter: bool = False, drop_resets: bool = False,
                glob_offset=0, left_idx=None, left_val=None):
    """Per-series rate of change on the shared bucket grid.

    Each nonempty bucket's rate is its backward difference against the
    series' previous nonempty bucket (bucket-start timestamps, so
    dt = (b - prev_b) * interval) — the downsample-then-rate composition
    the reference builds from iterators (SpanGroup.java:736-784 computes
    rates from consecutive downsampled points). The first nonempty bucket
    of a series yields no rate, matching oracle.rate.

    The optional carry args serve the time-sharded path: ``left_idx`` [S]
    is the series' nearest nonempty *global* bucket before this tile's
    window (-1 = none) and ``left_val`` its value; a tile-first bucket
    differences against that instead of having no predecessor.
    ``glob_offset`` maps local bucket indices to global ones.

    Returns (rates [S, B] float32, ok [S, B] bool).
    """
    S, B = series_values.shape
    b_idx = jnp.arange(B, dtype=jnp.int32)
    masked_idx = jnp.where(series_mask, b_idx[None, :], -1)
    prev_incl = jax.lax.cummax(masked_idx, axis=1)
    prev_excl = jnp.concatenate(
        [jnp.full((S, 1), -1, jnp.int32), prev_incl[:, :-1]], axis=1)
    has_local = prev_excl >= 0
    p = jnp.clip(prev_excl, 0, B - 1)
    prev_val = jnp.take_along_axis(series_values, p, axis=1)
    prev_glob = glob_offset + prev_excl
    if left_idx is not None:
        use_carry = ~has_local & (left_idx[:, None] >= 0)
        prev_glob = jnp.where(use_carry, left_idx[:, None], prev_glob)
        prev_val = jnp.where(use_carry, left_val[:, None], prev_val)
        has_prev = has_local | use_carry
    else:
        has_prev = has_local
    glob = glob_offset + b_idx[None, :]
    dt = jnp.maximum((glob - prev_glob).astype(jnp.float32) * interval,
                     1e-9)
    dv = series_values - prev_val
    if counter:
        dv = jnp.where(dv < 0, dv + counter_max, dv)
    r = dv / dt
    if drop_resets:
        r = jnp.where(jnp.abs(r) > reset_value, 0.0, r)
    ok = series_mask & has_prev
    return jnp.where(ok, r, 0.0), ok


def step_fill(series_values: jnp.ndarray, series_mask: jnp.ndarray,
              num_buckets: int, *, left_idx=None, left_val=None,
              right_idx=None):
    """Last-value-hold fill of empty buckets (the rate counterpart of
    gap_fill: rates step between points, SpanGroup.java:736-784 /
    oracle.group_aggregate(interp='step')).

    A series contributes its previous bucket's value in empty buckets
    between its first and last nonempty ones, nothing outside. The carry
    args serve the time-sharded path; unlike gap_fill, only presence and
    the *left* value matter to a step hold (no distances, no right
    value), so the global-index plumbing stops at the flags: ``left_idx``
    [S] >= 0 means the series has a nonempty bucket on an earlier tile
    with value ``left_val``; ``right_idx`` [S] < 2^31-1 means one exists
    on a later tile. Returns (filled [S, B], in_range [S, B]).
    """
    b_idx = jnp.arange(num_buckets, dtype=jnp.int32)
    prev_loc = jax.lax.cummax(
        jnp.where(series_mask, b_idx[None, :], -1), axis=1)
    next_loc = jax.lax.cummin(
        jnp.where(series_mask, b_idx[None, :], num_buckets), axis=1,
        reverse=True)
    has_prev_loc = prev_loc >= 0
    has_next_loc = next_loc < num_buckets
    p = jnp.clip(prev_loc, 0, num_buckets - 1)
    y0 = jnp.take_along_axis(series_values, p, axis=1)
    if left_idx is None:
        prev_ok = has_prev_loc
        prev_val = y0
    else:
        prev_ok = has_prev_loc | (left_idx[:, None] >= 0)
        prev_val = jnp.where(has_prev_loc, y0, left_val[:, None])
    if right_idx is None:
        next_ok = has_next_loc
    else:
        next_ok = has_next_loc | (right_idx[:, None] < _I32_BIG)
    in_range = prev_ok & next_ok
    filled = jnp.where(series_mask, series_values, prev_val)
    return jnp.where(in_range, filled, 0.0), in_range


def group_moments(filled: jnp.ndarray, in_range: jnp.ndarray):
    """Masked per-bucket moments across series (axis 0): count, total,
    centered M2, mean, min, max."""
    n = in_range.astype(jnp.float32).sum(axis=0)
    total = jnp.where(in_range, filled, 0.0).sum(axis=0)
    mean = total / jnp.maximum(n, 1.0)
    centered = jnp.where(in_range, filled - mean[None, :], 0.0)
    m2 = (centered * centered).sum(axis=0)
    mn = jnp.where(in_range, filled, _POS_INF).min(axis=0)
    mx = jnp.where(in_range, filled, _NEG_INF).max(axis=0)
    return n, total, m2, mean, mn, mx


# ---------------------------------------------------------------------------
# Device-window helpers (storage/devstore.py query path)
# ---------------------------------------------------------------------------

def _window_series_stage(rel_ts, vals, sid, valid_in, lo, hi, shift, *,
                         num_series, num_buckets, interval, agg_down,
                         rate=False, counter_max=0.0, reset_value=0.0,
                         counter=False, drop_resets=False):
    """The heavy, FILTER-INDEPENDENT half of any resident-window query:
    range masking + per-series downsample [+ rate] over the N resident
    points. No include mask, no gap fill, no grouping — so ONE cached
    device-resident stage serves every panel over the same (metric,
    range, interval, downsample): different tag filters, group-bys,
    group aggregators, moments AND quantiles all reuse it, paying only
    the [S, B]-sized apply per query: ~one dispatch per panel instead
    of an N-point scatter per panel (the devwindow serving pattern;
    the quantile path proved it first, this generalizes it to moments).

    Returns (series_values [S, B] post-rate, series_mask [S, B]
    post-rate, filled [S, B], in_range [S, B], presence [S] pre-rate)
    and after them, as window_series_stage_chunks does, the int32
    device scalar of the updates the stage's scatters were handed.
    ``filled``/``in_range`` carry the lerp (or, under rate, step) fill
    of the full grid: filling is ROW-LOCAL, so a series' filled row is
    identical whether or not other series are included — which makes
    the fill cacheable here rather than re-run per panel."""
    ok = valid_in & (rel_ts >= lo) & (rel_ts <= hi)
    out = downsample_group(
        rel_ts - shift, vals, sid, ok,
        num_series=num_series, num_buckets=num_buckets,
        interval=interval, agg_down=agg_down,
        agg_group="count", rate=rate, counter_max=counter_max,
        reset_value=reset_value, counter=counter,
        drop_resets=drop_resets)
    return _stage_tail(out["series_values"], out["series_mask"],
                       out["presence"], num_buckets=num_buckets,
                       rate=rate) + (out["handed"],)


def _group_stage(filled, in_range, series_mask, gmap, *, num_groups,
                 agg_group):
    """Cross-series aggregation of a (filled, masked) [S, B] grid into
    [G, B] — row-wise segment reductions (S vector updates, never a
    flat S*B scatter)."""
    if num_groups == 1:
        g_count, g_total, g_m2, _, g_mn, g_mx = group_moments(
            filled, in_range)
        gv = _finish(agg_group, g_count, g_total, g_m2, g_mn, g_mx)[None]
        gm = series_mask.any(axis=0)[None]
        return gv, gm
    need = _needs(agg_group)
    g_count = jax.ops.segment_sum(
        in_range.astype(jnp.float32), gmap, num_groups)
    v = jnp.where(in_range, filled, 0.0)
    g_total = g_m2 = g_mn = g_mx = None
    if "sum" in need or "m2" in need:
        g_total = jax.ops.segment_sum(v, gmap, num_groups)
    if "m2" in need:
        g_mean = g_total / jnp.maximum(g_count, 1.0)
        centered = jnp.where(in_range, filled - g_mean[gmap], 0.0)
        g_m2 = jax.ops.segment_sum(centered * centered, gmap,
                                   num_groups)
    if "min" in need:
        g_mn = jax.ops.segment_min(
            jnp.where(in_range, filled, _POS_INF), gmap, num_groups)
    if "max" in need:
        g_mx = jax.ops.segment_max(
            jnp.where(in_range, filled, _NEG_INF), gmap, num_groups)
    gv = _finish(agg_group, g_count, g_total, g_m2, g_mn, g_mx)
    gm = jax.ops.segment_sum(
        series_mask.astype(jnp.int32), gmap, num_groups) > 0
    return gv, gm


def _shrink_wrap(gv, gm, g_out, b_out, wire_bf16=False):
    """Clip apply outputs to the (64-quantized) live group/bucket counts
    and bit-pack the mask before the device->host fetch, so a wide
    group-by does not fetch its PADDED [G, B] grids (what the fetch
    costs is the ledger's fetch_ms). g_out/b_out are static
    (bounded recompiles: 64 quantization).

    ``wire_bf16`` additionally halves the [G, B] value payload by
    casting to bfloat16 ON DEVICE (opt-in via Config.wire_bf16: it
    trades the window path's byte-exactness vs the scan path for wire
    bytes — ~2-3 significant digits, plenty for dashboard pixels,
    wrong for billing). bfloat16, not float16: the float32 exponent
    range means big group sums can't overflow to inf (f16 tops out at
    65504)."""
    gv = gv[..., :g_out, :b_out]
    if wire_bf16:
        gv = gv.astype(jnp.bfloat16)
    gm = jnp.packbits(gm[:g_out, :b_out], axis=1)
    return gv, gm


def _moment_apply(series_values, series_mask, filled, in_range, include,
                  gmap, *, num_groups, agg_group,
                  g_out=None, b_out=None, wire_bf16=False):
    """Cheap per-query half of a resident-window MOMENT query: include
    masking (row-wise — identical to having filtered the points
    upstream, since fill is row-local) + group aggregation over the
    cached [S, B] stage grids."""
    sm = series_mask & include[:, None]
    if agg_group in NOLERP_AGGS:
        f, ir = series_values, sm
    else:
        f, ir = filled, in_range & include[:, None]
    gv, gm = _group_stage(f, ir, sm, gmap,
                          num_groups=num_groups, agg_group=agg_group)
    if g_out is None:
        return gv, gm
    return _shrink_wrap(gv, gm, g_out, b_out, wire_bf16)


def _quantile_apply(series_mask, filled, in_range,
                    include, gmap, q, *, num_groups,
                    g_out=None, b_out=None, wire_bf16=False):
    """Cheap per-quantile half: include masking + [G, B] masked
    quantiles from the cached stage's filled grid (quantiles always use
    the lerp/step fill family — reference SpanGroup percentile
    semantics)."""
    sm = series_mask & include[:, None]
    ir = in_range & include[:, None]
    if num_groups == 1:
        gv = masked_quantile_axis0(filled, ir, q)[:1]
        gm = sm.any(axis=0)[None]
    else:
        # host=* percentile dashboards: all groups' quantiles in the
        # same program (excluded/padded series carry no valid buckets,
        # so wherever gmap sends them they add nothing).
        gv = masked_quantile_groups(filled, ir, gmap, q,
                                    num_groups=num_groups)[0]
        gm = jax.ops.segment_sum(
            sm.astype(jnp.int32), gmap, num_groups) > 0
    if g_out is None:
        return gv, gm
    return _shrink_wrap(gv, gm, g_out, b_out, wire_bf16)


def _stage_tail(series_values, series_mask, presence, *, num_buckets,
                rate):
    """Shared tail of both window stages (concat + chunked): fill per
    the rate family and return the stage contract. One definition so
    the fill-choice semantics can't diverge between the two."""
    fill = step_fill if rate else gap_fill
    filled, in_range = fill(series_values, series_mask, num_buckets)
    return series_values, series_mask, filled, in_range, presence


# The run reduction of window.chunk_fold: a block is cut into tiles of
# _FOLD_TILE slots, and a turn of its inner loop hands the scatters
# _FOLD_RUNS runs of every tile (_scatter_runs). Both powers of two;
# a block shorter than a tile is one tile. Their ratio is what the
# scatters see (block * _FOLD_RUNS / _FOLD_TILE updates a turn, and
# _FOLD_TILE / _FOLD_RUNS turns for a block in no order at all), the
# tile what the vector unit sees (a turn compares every slot of a tile
# with _FOLD_RUNS run numbers). Settled on a v5e (PERF.md §6, PR 39):
# at this ratio tiles of 32 to 512 slots read alike within 15%, twice
# the ratio doubles an hourly block's time, and a quarter of it (512 x 8)
# takes an hourly block to 0.03 ms and a 1-min one back to 1.13.
_FOLD_TILE = 128
_FOLD_RUNS = 8


def _scatter_runs(part, v, ok, seg, dump, extra=None, total=None):
    """Fold one block's slots into the accumulators of ``part`` (a dict
    keyed by statistic: ``count`` the slots with ``ok``, ``sum`` /
    ``min`` / ``max`` of ``v`` under ``ok``, ``extra`` the sum of the
    feature ``extra`` under ``ok``), one scatter update a RUN
    of equal segment ids and not one a slot. ``total``, where given, is
    what ``sum`` adds up in place of ``v`` (the stage's runs folded
    beforehand, _run_fold). XLA:TPU applies a
    scatter's updates one after another (8.9 ns each on a v5e), and
    every writer stages a chunk in runs (a series-hour is 360
    consecutive slots of one series, so an hourly bucket's segment 360
    times in a row), so nearly all of a slot-wise scatter repeats its
    last index.

    The block is viewed as tiles of _FOLD_TILE slots; within a tile a
    slot whose segment differs from its predecessor's starts a run, and
    a running count numbers the tile's runs. A turn of the loop takes
    runs [j * _FOLD_RUNS, (j + 1) * _FOLD_RUNS) of EVERY tile: one
    masked reduction over the tile a statistic (compare, select and
    reduce fuse; no [tile, runs, tiles] tensor is written), the run's
    segment by a max under the same mask (no slot of that run number
    in the tile: the dump segment), and the same scatters as before
    over tiles x _FOLD_RUNS updates. The trip count is DATA, the worst
    tile's run count: one turn for hourly runs, a few for 1-min ones,
    _FOLD_TILE / _FOLD_RUNS for slots in no order, which are then the
    slot-wise scatter's updates and never more. Slots the range cut
    (``ok`` false, the dump segment) split a run, harmlessly. The sums
    are float32 sums of the slots themselves (no difference of prefix
    sums, which would cancel); count, min and max take no rounding, so
    they are the slot-wise scatter's bits for any order.

    Returns ``part`` updated and the updates each scatter was handed
    (int32: turns x tiles x _FOLD_RUNS)."""
    tile = min(_FOLD_TILE, seg.shape[0])
    runs = min(_FOLD_RUNS, tile)
    tiles = seg.shape[0] // tile
    # A tile a COLUMN, its slots down axis 0: the reductions of a turn
    # then run along the major axis and the tiles fill the lanes, which
    # is worth 11-16 us a turn on a v5e over a tile a row (a block in
    # no order: 0.95 ms against 1.20).
    v, ok, seg = (a.reshape(tiles, tile).T for a in (v, ok, seg))
    if "extra" in part:
        extra = extra.reshape(tiles, tile).T[:, None, :]
    if total is not None:
        total = total.reshape(tiles, tile).T[:, None, :]
    start = jnp.concatenate(
        [jnp.ones((1, tiles), bool), seg[1:] != seg[:-1]], axis=0)
    run = jnp.cumsum(start.astype(jnp.int32), axis=0) - 1
    turns = jnp.max(run[-1]) // runs + 1
    lanes = jnp.arange(runs, dtype=jnp.int32)[:, None]

    def turn(j, part):
        hit = (run - j * runs)[:, None, :] == lanes
        at = jnp.max(jnp.where(hit, seg[:, None, :], -1), axis=0).ravel()
        at = jnp.where(at < 0, dump, at)
        live = hit & ok[:, None, :]
        feed = v[:, None, :]
        part = dict(part)
        if "count" in part:
            part["count"] = part["count"].at[at].add(
                jnp.sum(live.astype(jnp.float32), axis=0).ravel())
        if "sum" in part:
            part["sum"] = part["sum"].at[at].add(
                jnp.sum(jnp.where(live, feed if total is None else total,
                                  0.0), axis=0).ravel())
        if "min" in part:
            part["min"] = part["min"].at[at].min(
                jnp.min(jnp.where(live, feed, _POS_INF), axis=0).ravel())
        if "max" in part:
            part["max"] = part["max"].at[at].max(
                jnp.max(jnp.where(live, feed, _NEG_INF), axis=0).ravel())
        if "extra" in part:
            part["extra"] = part["extra"].at[at].add(
                jnp.sum(jnp.where(live, extra, 0.0), axis=0).ravel())
        return part

    return (jax.lax.fori_loop(0, turns, turn, part),
            turns * (tiles * runs))


# The slots of a packed stream that _run_moments hands _scatter_runs at
# a time. _scatter_runs' trip count is its worst tile's run count, so a
# stretch of slots in no order costs the block it lies in sixteen turns
# and not the stream; a block's fixed cost is a pass over each
# accumulator (the scatters' operands), so it is the fold's block and
# not smaller.
_STAGE_BLOCK = 1 << 16

# The slots of a run that _run_fold adds up one after another before it
# begins a new partial sum. A left fold takes a step a slot whatever
# computes it, so a long run is cut every _STAGE_FOLD slots FROM ITS OWN
# FIRST SLOT (the last cut left out where under _FOLD_TILE slots would
# follow it): what a run sums to then follows from its values in their
# order alone, and a run of up to _STAGE_FOLD + _FOLD_TILE - 1 slots (a
# series-hour's 360) sums to the bits a slot-wise scatter gives it.
# Whole turns of _FOLD_STEPS rows; 384 because the TPU's compiler takes
# 3.4 s over the fleet-wide program so, 4.5 s at 512 and 10 s at 256
# (compiled for a described v5e on this repo's CPU host, PERF.md §6).
_STAGE_FOLD = 384
# The lanes the columns of _run_fold's scans are laid over (a vector
# register's), and the rows a turn of a scan takes: the scans that keep
# only what a column ends with make a turn three device operations,
# the one that keeps every sum one a row.
_LANES = 128
_FOLD_STEPS = 32


def _varying(x, like):
    """``x`` for the start of a loop's carry that is computed from
    ``like``: inside a shard_map the stream varies over the mesh's
    axes, and a carry has to vary from its start as its result will."""
    vma = tuple(jax.typeof(like).vma)
    return jax.lax.pcast(x, vma, to="varying") if vma else x


def _run_fold(x, head):
    """The sums of a stream's runs, each at its run's last slot and
    zero at every other. A run begins at a slot with ``head`` set (slot
    0 is one), and its sum is the left fold ((0 + x[a]) + x[a + 1]) +
    .. of its slots, float32 additions one after another as a
    slot-wise scatter makes them. A long run is pieces, each summed so:
    one begins every _STAGE_FOLD slots from the run's first, but for a
    last piece of under _FOLD_TILE slots, which stays with the one
    before it. So a piece's last slot is the first of its run in its
    tile of _FOLD_TILE slots or lies later, and no tile holds two: the
    scatters are handed a long run's pieces one an update, in the
    stream's order (_scatter_runs: run 0 of their tiles).

    The stream is laid a stretch of _STAGE_FOLD slots a COLUMN, and a
    ``scan`` goes down the rows: a step is one addition in every column
    at once, so the stream's length is the vector's and _STAGE_FOLD the
    steps. The columns lie over sublanes and lanes, a step a slab of
    whole tiles (two scans of 512 rows over the 20.97M slots of a
    fleet-wide request: 2.3 ms on a v5e so, 7.0 ms a row of the [rows,
    columns] matrix a step, which is one sublane of every tile), and a
    turn of a scan takes _FOLD_STEPS rows (a turn is device operations
    of its own, which a traced run's profile pays for one by one).
    Where a long run is cut follows from the column its first slot
    lies in, carried forward over the columns it fills (a cummax over
    the columns; over the slots it took the TPU 9 ms, and its compiler
    10 s). A column then holds a piece's first slot or lies whole in a
    piece that began in the column before, so what a column ends with
    is right after two scans, each begun with what the columns before
    ended the last with, and every slot after a third."""
    size = x.shape[0]
    rows = min(_STAGE_FOLD, -(-size // _FOLD_STEPS) * _FOLD_STEPS)
    cols = -(-size // (rows * _LANES)) * _LANES
    pad = cols * rows - size
    x = jnp.pad(x, (0, pad)).reshape(cols, rows).T
    head = jnp.pad(head, (0, pad), constant_values=True).reshape(cols, rows).T
    # Of the run a column begins in: the slots since its first, modulo
    # the rows (from the last head of the column before or, where that
    # holds none, what that one began at), and the row after its last
    # (past the rows: in the column after, or further). Column 0 begins
    # with a head, which also ends the last column's run for the rolls.
    row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    first = jnp.min(jnp.where(head, row, rows), axis=0)
    latest = jnp.max(jnp.where(head, row, -1), axis=0)
    at = jnp.arange(cols, dtype=jnp.int32) * rows
    since = jax.lax.cummax(
        jnp.where(latest < 0, -1, at + (rows - latest) % rows), axis=0)
    cut = (rows - jnp.roll(since, 1) % rows) % rows
    after = jnp.where(first < rows, first, rows + jnp.roll(first, -1))
    head = head | ((row == cut) & (row < first)
                   & (after - cut >= _FOLD_TILE))

    def steps(acc, slots):
        sums = []
        for x_row, head_row in zip(*slots):
            acc = jnp.where(head_row, 0.0, acc) + x_row
            sums.append(acc)
        return acc, jnp.stack(sums)

    def ends(acc, slots):
        return steps(acc, slots)[0], None

    slots = tuple(a.reshape(-1, _FOLD_STEPS, cols // _LANES, _LANES)
                  for a in (x, head))
    acc = _varying(jnp.zeros(slots[0].shape[2:], x.dtype), x)
    for turn in (ends, ends, steps):
        acc, out = jax.lax.scan(
            turn, jnp.roll(acc.reshape(-1), 1).reshape(acc.shape), slots)
    out, head = (a.reshape(rows, cols).T.reshape(-1)[:size]
                 for a in (out, head))
    return jnp.where(jnp.concatenate([head[1:], jnp.ones(1, bool)]),
                     out, 0.0)


def _run_moments(vals, seg, valid, num_segments, extra, need):
    """_segment_moments' statistics of a packed stream, its runs of
    equal segment id reduced before they are scattered (_scatter_runs,
    the routine of window.chunk_fold): what _series_stage takes them
    by. The stream is taken a block of at most _STAGE_BLOCK slots a
    turn of a ``fori_loop``; a length that is not whole tiles or blocks
    (the quarter-octave ladder's 320 and 448, a dense leg's K rows, a
    test's 16) is padded inside the program with slots of the last
    segment, which the caller keeps for its trash.

    count, min and max are the slot-wise scatters' bits. A float32 sum
    (``sum``, and ``m2``, the second pass, centered on the segment
    means) is a left fold of a run's valid slots in the order they lie
    (_run_fold; a long run in pieces of _STAGE_FOLD slots), and the
    scatters add each fold at its last slot and zero at every other: a
    run sums to the same bits wherever in a stream, a tile or a block
    it lies, so two plans that lay a (series, bucket) as the same run
    of points give the same answer bit for bit, and a run of one piece
    the slot-wise scatter's. A segment in several runs is their sums
    added as the turns come to them.
    ``extra`` (offsets in a bucket: whole numbers, exact while a sum
    stays under 2**24) is summed a tile at a time.

    Returns _segment_moments' tuple (un-needed statistics None), then
    the ``extra`` sum where ``extra`` is given, and last the updates
    each scatter of the first pass was handed (int32)."""
    dump = num_segments - 1
    size = max(seg.shape[0], 1)
    tile = min(_FOLD_TILE, size)
    # Equal blocks of whole tiles, none over _STAGE_BLOCK: the padding
    # is under a tile a block (a tail block padded to _STAGE_BLOCK
    # would cost a stream in no order a block's updates for nothing).
    blocks = -(-size // _STAGE_BLOCK)
    block = -(-size // (blocks * tile)) * tile
    pad = blocks * block - seg.shape[0]
    feeds = {"v": vals, "ok": valid, "seg": seg}
    if extra is not None:
        feeds["extra"] = extra
    if pad:
        feeds = {k: jnp.pad(a, (0, pad),
                            constant_values=dump if k == "seg" else 0)
                 for k, a in feeds.items()}
    v, ok, seg = feeds["v"], feeds["ok"], feeds["seg"]
    # A run for the folds: slots of one segment in a row, every invalid
    # slot one of its own (it adds nothing, and the trash segment's
    # stretches need no fold).
    head = ~ok | jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])

    def folded(x):
        return _run_fold(jnp.where(ok, x, 0.0), head)

    def start(fill, shape=(num_segments,), dtype=jnp.float32):
        return _varying(jnp.full(shape, fill, dtype), seg)

    def sweep(part, total):
        cols = dict(feeds) if total is None else dict(feeds, total=total)

        def turn(i, carry):
            part, handed = carry
            cut = {k: jax.lax.dynamic_slice_in_dim(a, i * block, block)
                   for k, a in cols.items()}
            part, n = _scatter_runs(part, cut["v"], cut["ok"], cut["seg"],
                                    dump, cut.get("extra"),
                                    cut.get("total"))
            return part, handed + n
        return jax.lax.fori_loop(0, blocks, turn,
                                 (part, start(0, (), jnp.int32)))

    zeros = start(0.0)
    part = {"count": zeros}
    summed = "sum" in need or "m2" in need
    if summed:
        part["sum"] = zeros
    if "min" in need:
        part["min"] = start(_POS_INF)
    if "max" in need:
        part["max"] = start(_NEG_INF)
    if extra is not None:
        part["extra"] = zeros
    part, handed = sweep(part, folded(v) if summed else None)
    m2 = None
    if "m2" in need:
        d = v - (part["sum"] / jnp.maximum(part["count"], 1.0))[seg]
        m2 = sweep({"sum": zeros}, folded(d * d))[0]["sum"]
    out = (part["count"], part.get("sum"), m2, part.get("min"),
           part.get("max"))
    if extra is not None:
        out += (part["extra"],)
    return out + (handed,)


# The chunks one call of window.chunk_fold takes: a stage folds its
# chunks _FOLD_GROUP at a time, shape class by shape class, so what the
# host issues for a stage is 2 + the sum over classes of
# ceil(chunks hit / _FOLD_GROUP) programs and not 7 + the chunks hit.
# A turn of the fold slices its block out of each of the _FOLD_GROUP
# operands and keeps one by a select, so the group costs a turn
# _FOLD_GROUP reads of a block where one would do. Settled on a v5e
# (PERF.md §6, PR 41): a block of 65,536 slots in hourly or 5-min runs
# takes 87-92 us alone, 94-97 in a group of 4 and 105-107 in a group of
# 8, which is more than the 15% the grouping was allowed to cost a turn.
_FOLD_GROUP = 4


@jit_plan(ExecPlan(
    name="window.chunk_stage_start", axis="series",
    static_argnames=("nseg",)))
def _chunk_stage_start(*, nseg):
    """What a stage's folds start from, as ONE program: the five
    accumulators (count, total, m2 at zero, min at +inf, max at -inf)
    and the ``handed`` scalar at zero. Compiled once a grid size."""
    zeros = jnp.zeros(nseg, jnp.float32)
    return (zeros, zeros, zeros, jnp.full(nseg, _POS_INF, jnp.float32),
            jnp.full(nseg, _NEG_INF, jnp.float32), jnp.zeros((), jnp.int32))


@jit_plan(ExecPlan(
    name="window.chunk_fold", axis="series",
    static_argnames=("num_series", "num_buckets", "interval", "need",
                     "block"),
    donate_argnums=(1, 2, 3, 4, 5)))
def _chunk_fold(chunks, count, total, m2, mn, mx, handed, visit, *,
                num_series, num_buckets, interval, need, block):
    """Fold the selected blocks of a GROUP of resident chunks of one
    shape class into the per-(series, bucket) accumulators. ``chunks``
    is a tuple of (rel_ts, vals, sid, valid) tuples, all of one shape
    (the stage driver hands over _FOLD_GROUP of them, a short group's
    empty places filled with its first chunk again: no memory, never
    selected). ``visit`` is one int32 vector, ``[lo, hi, shift, n,
    (which_0, id_0) .. (which_n-1, id_n-1), 0 ..]``: the range, the
    bucket shift, and the ``n`` blocks of ``block`` slots to visit,
    each as the operand it lies in and its id there (the devwindow's
    zone-map selection, DevChunks.blocks, of every chunk of the group
    as one list), padded to the group's block count. All of it is DATA,
    so a range never seen before runs the program already compiled: a
    ``fori_loop`` takes one block a turn (its slice out of every
    operand, one kept by a select on the scalar ``which_i``: the body
    is traced once whatever the group's size, and nothing branches),
    reduces its runs of equal (series, bucket) to one value each
    and scatters the runs into the call's partial statistics
    (_scatter_runs). What a turn costs follows the RUNS of the slots it
    is handed, not the points in range: on a v5e (PERF.md §6, PR 39)
    0.09 ms a block of 65,536 slots in hourly or 5-min buckets (one
    turn, 4,096 updates a scatter), 0.31 ms in 1-min buckets (three
    turns, 12,288) and 0.95 ms for slots in no order (sixteen, 65,536),
    where the slot-wise scatters it replaced took 1.18, 1.25 and 0.91
    ms. A scatter also costs a pass over its operand, about 40 us at
    the 4M segments of a 1-min grid, which is most of that case's turn.
    ``handed`` is an int32 scalar carried through a stage's folds
    beside the accumulators: the updates the scatters were handed
    (tsd.devwindow.fold.updates). One vector and not five arguments
    because every host array argument is its own host-to-device
    transfer at dispatch (measured on a v5e, PERF.md §6: 0.35 ms a fold
    against 1.1 ms), and one call a group and not one a chunk because
    every program a stage issues from Python is a place where its
    thread lets the interpreter lock go and has to win it back
    (PERF.md §6, PR 41). Compiled once per chunk shape class (chunks
    are pow2-padded, so there are only a handful); accumulators are
    donated so the fold is in-place. The stage driver issues the calls
    back-to-back ASYNC: dispatch does not wait for the device.

    ``m2`` accumulates the exact pairwise (Chan et al.) combination,
    once a call: the group's M2 is centered on the GROUP-local segment
    means (a second turn over the same blocks), then corrected by the
    mean shift against the running accumulator — numerically sound
    where a naive E[x^2]-E[x]^2 merge cancels catastrophically (same
    scheme as the sharded psum fan-in, parallel/sharded.py)."""
    lo, hi, shift, n_blocks = visit[0], visit[1], visit[2], visit[3]
    picks = visit[4:].reshape(-1, 2)
    nseg = num_series * num_buckets + 1

    def block_of(i):
        which, at = picks[i, 0], picks[i, 1] * block
        r, v, s, ok = (jax.lax.select_n(which, *(
            jax.lax.dynamic_slice_in_dim(c, at, block) for c in column))
            for column in zip(*chunks))
        ok = ok & (r >= lo) & (r <= hi)
        bucket = jnp.clip((r - shift) // interval, 0, num_buckets - 1)
        return v, ok, jnp.where(ok, s * num_buckets + bucket, nseg - 1)

    def moments(i, carry):
        part, handed = carry
        part, n = _scatter_runs(part, *block_of(i), nseg - 1)
        return part, handed + n

    zeros = jnp.zeros(nseg, jnp.float32)
    part = {"count": zeros}
    if "sum" in need or "m2" in need:
        part["sum"] = zeros
    if "min" in need:
        part["min"] = jnp.full(nseg, _POS_INF, jnp.float32)
    if "max" in need:
        part["max"] = jnp.full(nseg, _NEG_INF, jnp.float32)
    part, handed = jax.lax.fori_loop(0, n_blocks, moments, (part, handed))
    c_cnt, c_tot = part["count"], part.get("sum")
    if "m2" in need:
        c_mean = c_tot / jnp.maximum(c_cnt, 1.0)

        def centered(i, c_m2):
            v, ok, seg = block_of(i)
            d = v - c_mean[seg]
            return _scatter_runs({"sum": c_m2}, d * d, ok, seg,
                                 nseg - 1)[0]["sum"]

        c_m2 = jax.lax.fori_loop(0, n_blocks, centered, zeros)
        # Chan combine with the running (count, total, m2): the
        # mean-shift correction uses the PRE-update accumulator.
        a_cnt = count
        a_mean = total / jnp.maximum(a_cnt, 1.0)
        tot_n = a_cnt + c_cnt
        delta = c_mean - a_mean
        corr = jnp.where(tot_n > 0,
                         delta * delta * a_cnt * c_cnt
                         / jnp.maximum(tot_n, 1.0), 0.0)
        m2 = m2 + c_m2 + corr
    count = count + c_cnt
    if c_tot is not None:
        total = total + c_tot
    if "min" in need:
        mn = jnp.minimum(mn, part["min"])
    if "max" in need:
        mx = jnp.maximum(mx, part["max"])
    return count, total, m2, mn, mx, handed


@jit_plan(ExecPlan(
    name="window.chunk_stage_finish", axis="series",
    static_argnames=("num_series", "num_buckets", "interval", "agg_down")
    + _RATE_STATICS))
def _chunk_stage_finish(count, total, m2, mn, mx, *, num_series,
                        num_buckets, interval, agg_down, rate=False,
                        counter_max=0.0, reset_value=0.0, counter=False,
                        drop_resets=False):
    need = _needs(agg_down)
    per = _finish(agg_down, count,
                  total if ("sum" in need or "m2" in need) else None,
                  m2 if "m2" in need else None,
                  mn if "min" in need else None,
                  mx if "max" in need else None)
    shape = (num_series, num_buckets)
    series_values = per[:-1].reshape(shape)
    series_mask = count[:-1].reshape(shape) > 0
    presence = series_mask.any(axis=1)  # pre-rate, like downsample_group
    if rate:
        series_values, series_mask = bucket_rate(
            series_values, series_mask, interval, counter_max,
            reset_value, counter=counter, drop_resets=drop_resets)
    return _stage_tail(series_values, series_mask, presence,
                       num_buckets=num_buckets, rate=rate)


# The largest grid (series x buckets, both as the executor pads them)
# a resident stage is built for: 4,096 series at 4,096 buckets, or
# 65,536 at 256. The executor's resident plan declines a request above
# it (the scan path serves that one in per-group grids), and the
# daemon's boot check keeps room for one such stage beside the window,
# so what the check reserved for is what is served.
STAGE_GRID_MAX = 1 << 24


def stage_accumulator_bytes(cells: int = STAGE_GRID_MAX) -> int:
    """What the chunked stage's accumulators hold on the device while
    it folds a grid of ``cells``: the five float32 vectors
    _chunk_stage_start allocates (count, total, m2, min, max),
    one segment a cell and the dump segment."""
    return 5 * 4 * (cells + 1)


def fold_groups(chunks, blocks=None, block=None):
    """The calls of window.chunk_fold a stage over this selection
    issues, as ``[(block, [(chunk, picked), ..]), ..]``: the chunks with
    a block picked, gathered by shape class in the order the classes
    first appear in ``chunks`` and cut into groups of at most
    _FOLD_GROUP. It follows nothing but the shapes of the chunk list
    and the blocks picked: a list of one chunk is one group of one."""
    classes = {}
    for i, chunk in enumerate(chunks):
        picked = (0,) if blocks is None else blocks[i]
        if len(picked):
            classes.setdefault(chunk[0].shape[0], []).append((chunk, picked))
    return [(slots if blocks is None else min(block, slots),
             members[at:at + _FOLD_GROUP])
            for slots, members in classes.items()
            for at in range(0, len(members), _FOLD_GROUP)]


def window_series_stage_chunks(chunks, lo, hi, shift, *, num_series,
                               num_buckets, interval, agg_down,
                               blocks=None, block=None, device=None,
                               rate=False, counter_max=0.0,
                               reset_value=0.0, counter=False,
                               drop_resets=False):
    """window_series_stage over the devwindow's RAW CHUNK LIST — no
    concatenated copy of the columns ever exists, so a queryable window
    can approach the chip's WHOLE HBM (the concat view costs a second
    full copy plus N-sized transients, capping it near half — the
    1B-points-resident north star, BASELINE.md).

    Structure: a start program (_chunk_stage_start: the accumulators),
    one fold jit a GROUP of up to _FOLD_GROUP chunks of one shape class
    (fold_groups; compiled once per pow2 chunk shape class, NOT one
    giant unrolled program that would retrace on every chunk-count
    change) driven by a host loop, and the finish program: 2 + the
    fold calls device programs from Python a stage. Async dispatch
    pipelines the folds on device and only the finish stage joins.
    Accumulators are donated, so peak HBM is the resident chunks + one
    accumulator set + one call's transients.

    Every moment family merges exactly (dev via the group-locally-
    centered M2 + Chan mean-shift correction — see _chunk_fold).

    ``chunks``: iterable of (rel_ts, values, sid, valid) tuples.
    ``blocks`` / ``block``: the devwindow's zone-map selection for
    [lo, hi] (DevChunks.blocks / .block) — per chunk, the ids of the
    ``block``-slot blocks whose recorded [min, max] timestamp meets the
    range. A chunk with none is in no call at all, and of the
    others only the listed blocks are visited; a block left out holds
    nothing but slots the fold would have sent to its dump segment, so
    the grids are the same for any order of data. Without ``blocks``
    every chunk is folded whole, as one block. count, min and max are
    the same bits however the chunks are grouped; the float32 sums add
    the same slots in another order of partial sums. ``device``: the
    stage starts there and stays there, folds or no folds (the sharded
    window's stages, each on its shard's device; None: where the
    chunks' committed columns take it, and a stage of no fold to the
    default device).
    Returns the window_series_stage contract, (series_values,
    series_mask, filled, in_range, presence), and after it the int32
    device scalar the folds carried: the updates their scatters were
    handed (_scatter_runs), for whoever counts them to fetch when it
    likes."""
    need = _needs(agg_down)
    # Unused statistics still flow through the fold signature (static
    # ``need`` gates their updates to no-ops) so one jit serves every
    # mergeable aggregator per shape class.
    if device is None:
        acc = _chunk_stage_start(nseg=num_series * num_buckets + 1)
    else:
        # Made on the device and committed to it: the folds, the finish
        # and what the caller does with the grids are then one set of
        # programs there, whether or not a chunk is folded.
        with jax.default_device(device):
            acc = jax.device_put(
                _chunk_stage_start(nseg=num_series * num_buckets + 1),
                device)
    for blk, members in fold_groups(chunks, blocks, block):
        first = members[0][0]
        # The vector's length follows the class's block count and the
        # group's places, whatever was picked and however many chunks
        # came: the shape, and so the program, follows the chunks'
        # shape class alone.
        visit = np.zeros(
            4 + 2 * _FOLD_GROUP * (first[0].shape[0] // blk), np.int32)
        picks, at = visit[4:].reshape(-1, 2), 0
        for which, (_chunk, picked) in enumerate(members):
            rows = picks[at:at + len(picked)]
            rows[:, 0], rows[:, 1] = which, picked
            at += len(picked)
        visit[:4] = lo, hi, shift, at
        operands = tuple(chunk for chunk, _picked in members) + (
            first,) * (_FOLD_GROUP - len(members))
        acc = _chunk_fold(
            operands, *acc, visit, num_series=num_series,
            num_buckets=num_buckets, interval=interval, need=need,
            block=blk)
    return _chunk_stage_finish(
        *acc[:5], num_series=num_series, num_buckets=num_buckets,
        interval=interval, agg_down=agg_down, rate=rate,
        counter_max=counter_max, reset_value=reset_value,
        counter=counter, drop_resets=drop_resets) + (acc[5],)


@jit_plan(ExecPlan(name="window.shard_combine", axis="series"))
def shard_combine(parts, rows):
    """The stage grids of a sharded window's shards (storage/devshard.py)
    joined into the grids one window would have given: ``parts`` is, a
    shard, the five grids of its window_series_stage_chunks, every
    shard's of one padded shape and all on one device; ``rows`` [S]
    int32 names, for each row of the joined grids, its row in the
    shards' grids laid end to end, and a row past their end for a row
    that is padding (zeros and False: what a stage gives a series id
    nobody has). Which rows go where is DATA: one program joins every
    metric whose shards pad alike, whatever each shard holds of it."""
    return tuple(jnp.take(jnp.concatenate(grids), rows, axis=0,
                          mode="fill", fill_value=0)
                 for grids in zip(*parts))


WINDOW_STAGE_PLAN = ExecPlan(
    name="window.stage", axis="series",
    static_argnames=("num_series", "num_buckets", "interval",
                     "agg_down") + _RATE_STATICS)
WINDOW_MOMENT_APPLY_PLAN = ExecPlan(
    name="window.moment_apply", axis="series",
    static_argnames=("num_groups", "agg_group", "g_out", "b_out",
                     "wire_bf16"))
WINDOW_QUANTILE_APPLY_PLAN = ExecPlan(
    name="window.quantile_apply", axis="series",
    static_argnames=("num_groups", "g_out", "b_out", "wire_bf16"))

window_series_stage = compile_with_plan(_window_series_stage,
                                        WINDOW_STAGE_PLAN)
window_moment_apply = compile_with_plan(_moment_apply,
                                        WINDOW_MOMENT_APPLY_PLAN)
window_quantile_apply = compile_with_plan(_quantile_apply,
                                          WINDOW_QUANTILE_APPLY_PLAN)


# ---------------------------------------------------------------------------
# Fused downsample + group-by (the hot query kernel)
# ---------------------------------------------------------------------------

def _series_stage(ts, vals, sid, valid, *, num_series, num_buckets,
                  interval, agg_down, with_ts: bool):
    """Shared per-(series, bucket) downsample stage: one fused segment
    reduction producing series_values/series_mask [S, B] (and, when
    ``with_ts``, per-bucket integer-mean member timestamps), and the
    updates its scatters were handed (an int32 device scalar:
    tsd.query.stage.updates).

    Negative result from before PR 1 (its record is gone with the
    transport it was taken through): a scatter-free formulation for
    (sid, ts)-sorted columns — int32/fixed-point-int64 prefix sums +
    searchsorted of the [S*B] grid — lost to the XLA scatter on TPU and
    CPU alike, because the grid-side searchsorted costs more than the
    scatter it replaces. The scatter path stays, and since PR 46 it is
    handed a stream's RUNS of equal (series, bucket) and not its slots
    (_run_moments, the resident fold's _scatter_runs): every packer
    lays a series' points together in time order, so an hourly bucket
    is 360 slots in a row, and the scatters of a fleet-wide 12 h
    request (20.97M slots) get a sixteenth of the updates. A run's
    float32 sum is its slots added one after another as before
    (_run_fold), so where a plan lays the run moves no bit of it."""
    bucket = jnp.clip(ts // interval, 0, num_buckets - 1)
    seg = jnp.where(valid, sid * num_buckets + bucket,
                    num_series * num_buckets)
    nseg = num_series * num_buckets + 1  # +1 trash segment for padding
    # Mean member timestamp rides the same reduction pass, relative
    # to bucket start for f32 exactness.
    rel = (ts - bucket * interval).astype(jnp.float32) if with_ts else None
    count, total, sumsq, mn, mx, *rel_sum, handed = _run_moments(
        vals, seg, valid, nseg, extra=rel, need=_needs(agg_down))
    per = _finish(agg_down, count, total, sumsq, mn, mx)
    shape = (num_series, num_buckets)
    series_values = per[:-1].reshape(shape)
    series_mask = count[:-1].reshape(shape) > 0
    if not with_ts:
        return series_values, series_mask, None, handed
    mean_rel = jnp.floor(rel_sum[0] / jnp.maximum(count, 1.0))
    bucket_starts = (jnp.arange(num_buckets, dtype=jnp.int32) * interval)
    series_ts = bucket_starts[None, :] + mean_rel[:-1].reshape(shape) \
        .astype(jnp.int32)
    return series_values, series_mask, series_ts, handed

@jit_plan(ExecPlan(
    name="downsample.group", axis="series",
    static_argnames=("num_series", "num_buckets", "interval", "agg_down",
                     "agg_group") + _RATE_STATICS))
def downsample_group(ts: jnp.ndarray, vals: jnp.ndarray, sid: jnp.ndarray,
                     valid: jnp.ndarray, *, num_series: int,
                     num_buckets: int, interval: int, agg_down: str,
                     agg_group: str, rate: bool = False,
                     counter_max: float = 0.0, reset_value: float = 0.0,
                     counter: bool = False, drop_resets: bool = False):
    """Downsample every series into aligned buckets, then aggregate across
    series — one fused computation.

    Args:
      ts:    [N] int32 offsets from the query start (bucket-aligned base).
      vals:  [N] float32 point values.
      sid:   [N] int32 series index in [0, num_series).
      valid: [N] bool padding mask.
      interval: bucket width (seconds); num_buckets: static bucket count
        covering the query range.

    Returns dict with:
      series_values [S, B] per-series downsampled buckets,
      series_ts     [S, B] int32 mean member-timestamp offset per bucket,
      series_mask   [S, B] bool bucket-nonempty mask,
      group_values  [B] cross-series aggregate (over nonempty buckets),
      group_mask    [B] bool,
      handed        int32 scalar, the updates the series stage's
        scatters were handed (_series_stage).

    Semantics parity: aligned buckets + integer-mean member timestamps =
    oracle.downsample(mode='aligned', bucket_ts='avg'); cross-series
    aggregation on the shared bucket grid = the lerp-free fast path
    (identical grids need no interpolation).

    ``rate=True`` inserts the rate stage between downsample and group
    (reference pipeline order: SGIterator computes rates from consecutive
    downsampled points, SpanGroup.java:736-784): series_values/series_mask
    become the per-bucket rates and their validity (each series' first
    nonempty bucket yields none), and the group stage step-fills instead
    of lerping — all still one fused computation.
    """
    series_values, series_mask, series_ts, handed = _series_stage(
        ts, vals, sid, valid, num_series=num_series,
        num_buckets=num_buckets, interval=interval, agg_down=agg_down,
        with_ts=True)
    # Pre-rate: "series has any valid point", free from the bucket grid
    # — a separate segment reduction over the N points (series_presence)
    # would cost a second N-sized scatter pass.
    presence = series_mask.any(axis=1)
    if rate:
        series_values, series_mask = bucket_rate(
            series_values, series_mask, interval, counter_max,
            reset_value, counter=counter, drop_resets=drop_resets)

    # Group stage: aggregate across series on the shared bucket grid.
    # The no-lerp family skips gap filling: a series only contributes
    # where it actually has a bucket. Rates step-hold; plain values lerp.
    if agg_group in NOLERP_AGGS:
        filled, in_range = series_values, series_mask
    elif rate:
        filled, in_range = step_fill(series_values, series_mask,
                                     num_buckets)
    else:
        filled, in_range = gap_fill(series_values, series_mask,
                                    num_buckets)
    g_count, g_total, g_m2, _, g_mn, g_mx = group_moments(filled, in_range)
    group_values = _finish(agg_group, g_count, g_total, g_m2, g_mn, g_mx)

    return {
        "series_values": series_values,
        "series_ts": series_ts,
        "series_mask": series_mask,
        "presence": presence,
        "group_values": group_values,
        # Emit only buckets where some series has a real point (the union
        # grid); filled contributions never create grid points. With rate,
        # "real" means a real rate (first points emit none).
        "group_mask": series_mask.any(axis=0),
        "handed": handed,
    }


@jit_plan(ExecPlan(
    name="downsample.multigroup", axis="series",
    static_argnames=("num_series", "num_groups", "num_buckets",
                     "interval", "agg_down", "agg_group")
    + _RATE_STATICS))
def downsample_multigroup(ts: jnp.ndarray, vals: jnp.ndarray,
                          sid: jnp.ndarray, valid: jnp.ndarray,
                          group_of_sid: jnp.ndarray, *, num_series: int,
                          num_groups: int, num_buckets: int, interval: int,
                          agg_down: str, agg_group: str,
                          rate: bool = False, counter_max: float = 0.0,
                          reset_value: float = 0.0, counter: bool = False,
                          drop_resets: bool = False):
    """Fused downsample + group-by for MANY group-by buckets in ONE call.

    The reference materializes one SpanGroup per distinct group-by tag
    combination and iterates them sequentially (TsdbQuery.java:294-363);
    a wide ``host=*`` query therefore costs G separate aggregations. Here
    all G groups ride two segment reductions: per-(series, bucket)
    downsample, then per-(group, bucket) moments with ``group_of_sid``
    [S] mapping each series to its group.

    Args as downsample_group, plus group_of_sid [S] int32 in
    [0, num_groups). Returns dict with group_values / group_mask shaped
    [G, B] (and ``handed``, as downsample_group). Semantics per group
    are identical to calling downsample_group on that group's series
    alone.
    """
    series_values, series_mask, _, handed = _series_stage(
        ts, vals, sid, valid, num_series=num_series,
        num_buckets=num_buckets, interval=interval, agg_down=agg_down,
        with_ts=False)
    presence = series_mask.any(axis=1)  # pre-rate, see downsample_group
    if rate:
        series_values, series_mask = bucket_rate(
            series_values, series_mask, interval, counter_max,
            reset_value, counter=counter, drop_resets=drop_resets)

    if agg_group in NOLERP_AGGS:
        filled, in_range = series_values, series_mask
    elif rate:
        filled, in_range = step_fill(series_values, series_mask,
                                     num_buckets)
    else:
        filled, in_range = gap_fill(series_values, series_mask,
                                    num_buckets)

    group_values, group_mask = _group_stage(
        filled, in_range, series_mask, group_of_sid,
        num_groups=num_groups, agg_group=agg_group)
    return {
        "group_values": group_values,
        "group_mask": group_mask,
        "series_values": series_values,
        "series_mask": series_mask,
        "presence": presence,
        "handed": handed,
    }


def _order_key(vals: jnp.ndarray) -> jnp.ndarray:
    """Monotone f32 -> uint32 mapping (IEEE total order): x < y iff
    key(x) < key(y). Negative floats flip all bits, non-negative set the
    sign bit — the classic radix-sort float trick."""
    b = jax.lax.bitcast_convert_type(vals, jnp.uint32)
    return jnp.where((b >> 31).astype(bool), ~b,
                     b | jnp.uint32(0x80000000))


def _key_to_float(key: jnp.ndarray) -> jnp.ndarray:
    """Inverse of _order_key."""
    neg = (key >> 31) == 0
    b = jnp.where(neg, ~key, key & jnp.uint32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(b, jnp.float32)


@jit_plan(ExecPlan(name="quantile.axis0", axis="series"))
def masked_quantile_axis0(vals: jnp.ndarray, mask: jnp.ndarray,
                          q: jnp.ndarray):
    """Per-column quantiles across series (axis 0) with a validity mask.

    Matches numpy's default linear interpolation: position (n-1)*q between
    the sorted valid values of each column. Columns with no valid entries
    return 0. ``q`` is a [K] array; returns [K, B].

    Implementation is a vectorized MSB-first radix SELECT, not a sort:
    32 masked-count passes over [S, B] find each column's rank-k key
    exactly. XLA's variable sort on a 16k-row axis costs ~1.1 s on one
    CPU core and is no better on TPU (sorts don't map to the VPU);
    the counting passes are pure masked reductions and run ~10x faster
    on CPU, and at memory speed on TPU (measured: 16384x256 select
    115 ms vs 1100 ms sort, CPU). Exactness: the selected key is a
    bit-exact rank statistic, so results match the sort-based form
    bit for bit.
    """
    keys = jnp.where(mask, _order_key(vals), jnp.uint32(0xFFFFFFFF))
    n = mask.sum(axis=0)  # [B]

    def kth(k):
        """Key of rank ``k`` [B] (0-indexed among valid entries)."""
        def body(i, carry):
            prefix, kk = carry
            bit = 31 - i
            # (x >> bit) >> 1 == x >> (bit+1) without a 32-bit shift.
            m_hi = ((keys >> bit) >> 1) == ((prefix >> bit) >> 1)[None, :]
            bit0 = ((keys >> bit) & 1) == 0
            c0 = (mask & m_hi & bit0).sum(axis=0)
            take1 = kk >= c0
            return (jnp.where(take1, prefix | (jnp.uint32(1) << bit),
                              prefix),
                    jnp.where(take1, kk - c0, kk))
        prefix, _ = jax.lax.fori_loop(
            0, 32, body, (jnp.zeros_like(k, jnp.uint32), k))
        return prefix

    def one(qi):
        pos = jnp.maximum(n - 1, 0).astype(jnp.float32) * qi
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        key_lo = kth(lo)
        vlo = _key_to_float(key_lo)
        # Rank hi's value: with duplicates spanning rank hi it is still
        # key_lo (count-of-<=key_lo exceeds hi); otherwise the smallest
        # valid key strictly above key_lo.
        cle = (mask & (keys <= key_lo[None, :])).sum(axis=0)
        above = jnp.min(
            jnp.where(mask & (keys > key_lo[None, :]), keys,
                      jnp.uint32(0xFFFFFFFF)), axis=0)
        vhi = jnp.where(hi < cle, vlo, _key_to_float(above))
        out = vlo + (pos - lo) * (vhi - vlo)
        return jnp.where(n > 0, out, 0.0)

    return jax.vmap(one)(jnp.atleast_1d(jnp.asarray(q, jnp.float32)))


@jit_plan(ExecPlan(name="quantile.groups", axis="series",
                   static_argnames=("num_groups",)))
def masked_quantile_groups(vals: jnp.ndarray, mask: jnp.ndarray,
                           gmap: jnp.ndarray, q: jnp.ndarray, *,
                           num_groups: int):
    """Per-(group, bucket) quantiles across member series, all groups in
    one call: the percentile form of the multigroup group stage.
    ``gmap`` [S] maps each series row to its group; semantics per group
    match masked_quantile_axis0 on that group's rows alone.

    ONE segmented 2-key sort does all the work: each column sorts by
    (group, value-order-key), which lays every (group, bucket)'s valid
    members out as a contiguous ascending run at a COLUMN-INDEPENDENT
    row offset (group sizes come from gmap alone), so rank selection is
    two take_along_axis gathers + a lerp. This replaced a 32-pass
    radix-select whose per-bit [S, B] segment reductions dominated
    grouped-percentile latency ~10x on TPU, and replaces the
    sequential per-group kernel loop the reference's SpanGroup
    materialization forces (src/core/TsdbQuery.java:294-363).
    Returns [K, G, B].
    """
    S, B = vals.shape
    keys = jnp.where(mask, _order_key(vals), jnp.uint32(0xFFFFFFFF))
    gcol = jnp.broadcast_to(gmap[:, None], (S, B)).astype(jnp.int32)
    # Lexicographic segmented sort along the series axis: primary key
    # group, secondary key value order; invalid entries sink to each
    # group's tail (key 0xFFFFFFFF).
    _, skeys = jax.lax.sort((gcol, keys), dimension=0, num_keys=2)
    svals = _key_to_float(skeys)
    # Column-independent group layout: group g's rows start at the
    # exclusive prefix of group sizes.
    sizes = jax.ops.segment_sum(jnp.ones_like(gmap, jnp.int32), gmap,
                                num_groups)
    starts = jnp.cumsum(sizes) - sizes                       # [G]
    n = jax.ops.segment_sum(mask.astype(jnp.int32), gmap,
                            num_groups)                      # [G, B]

    def one(qi):
        pos = jnp.maximum(n - 1, 0).astype(jnp.float32) * qi
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        idx_lo = jnp.clip(starts[:, None] + lo, 0, S - 1)    # [G, B]
        idx_hi = jnp.clip(starts[:, None] + hi, 0, S - 1)
        vlo = jnp.take_along_axis(svals, idx_lo, axis=0)
        vhi = jnp.take_along_axis(svals, idx_hi, axis=0)
        out = vlo + (pos - lo) * (vhi - vlo)
        return jnp.where(n > 0, out, 0.0)

    return jax.vmap(one)(jnp.atleast_1d(jnp.asarray(q, jnp.float32)))


@jit_plan(ExecPlan(
    name="downsample.multigroup_quantile", axis="series",
    static_argnames=("num_series", "num_groups", "num_buckets",
                     "interval", "agg_down") + _RATE_STATICS))
def downsample_multigroup_quantile(
        ts: jnp.ndarray, vals: jnp.ndarray, sid: jnp.ndarray,
        valid: jnp.ndarray, group_of_sid: jnp.ndarray, q: jnp.ndarray, *,
        num_series: int, num_groups: int, num_buckets: int, interval: int,
        agg_down: str, rate: bool = False, counter_max: float = 0.0,
        reset_value: float = 0.0, counter: bool = False,
        drop_resets: bool = False):
    """Fused downsample [+ rate] + per-group PERCENTILE aggregation for
    many group-by buckets in one call — the percentile sibling of
    downsample_multigroup (which is moment-only), closing the host=*
    p99 dashboard's per-group kernel loop.

    Per-group semantics are identical to downsample_group + the
    single-group quantile path on that group's series alone: series
    stage, optional bucket rates, gap/step fill between each series'
    real buckets, then the quantile across member series' contributions.
    Returns dict with group_values [G, B] (quantile ``q[0]``),
    group_mask [G, B], series_values, series_mask, handed.
    """
    series_values, series_mask, _, handed = _series_stage(
        ts, vals, sid, valid, num_series=num_series,
        num_buckets=num_buckets, interval=interval, agg_down=agg_down,
        with_ts=False)
    if rate:
        series_values, series_mask = bucket_rate(
            series_values, series_mask, interval, counter_max,
            reset_value, counter=counter, drop_resets=drop_resets)
    fill = step_fill if rate else gap_fill
    filled, in_range = fill(series_values, series_mask, num_buckets)
    gv = masked_quantile_groups(filled, in_range, group_of_sid, q,
                                num_groups=num_groups)
    real = jax.ops.segment_sum(
        series_mask.astype(jnp.int32), group_of_sid, num_groups) > 0
    return {
        "group_values": gv[0],
        "group_mask": real,
        "series_values": series_values,
        "series_mask": series_mask,
        "handed": handed,
    }


# ---------------------------------------------------------------------------
# Rate (flat layout)
# ---------------------------------------------------------------------------

def _flat_rate(ts, vals, sid, valid, counter_max, reset_value, *,
               counter: bool, drop_resets: bool, carry_ts=None,
               carry_val=None, use_carry=None):
    """Core of flat_rate; see its docstring. The optional carry args serve
    the time-sharded path (parallel/timeshard.py): where ``use_carry`` [N]
    is set, the point's predecessor is (carry_ts, carry_val) [N] — the
    series' last point on an earlier time tile — instead of the rolled
    neighbor, keeping counter/reset/epsilon semantics in this one place.
    """
    prev_ts = jnp.roll(ts, 1)
    prev_v = jnp.roll(vals, 1)
    prev_sid = jnp.roll(sid, 1)
    prev_valid = jnp.roll(valid, 1)
    ok = valid & prev_valid & (prev_sid == sid)
    ok = ok.at[0].set(False)
    if use_carry is not None:
        prev_ts = jnp.where(use_carry, carry_ts, prev_ts)
        prev_v = jnp.where(use_carry, carry_val, prev_v)
        ok = ok | use_carry
    dt = jnp.maximum((ts - prev_ts).astype(jnp.float32), 1e-9)
    dv = vals - prev_v
    if counter:
        dv = jnp.where(dv < 0, dv + counter_max, dv)
    r = dv / dt
    if drop_resets:
        r = jnp.where(jnp.abs(r) > reset_value, 0.0, r)
    return jnp.where(ok, r, 0.0), ok


@jit_plan(ExecPlan(name="rate.flat", axis="series",
                   static_argnames=("counter", "drop_resets")))
def flat_rate(ts: jnp.ndarray, vals: jnp.ndarray, sid: jnp.ndarray,
              valid: jnp.ndarray, counter_max: float = 0.0,
              reset_value: float = 0.0, *, counter: bool = False,
              drop_resets: bool = False):
    """Per-point rate of change within each series, in flat layout.

    Requires points sorted by (sid, ts) — the natural scan order. The first
    point of each series yields no rate (its valid bit clears), matching
    oracle.rate. ``counter`` adds rollover correction at counter_max;
    ``drop_resets``/reset_value zeroes implausible spikes.

    Returns (rates [N] float32 emitted at each point's own ts, valid [N]).
    """
    return _flat_rate(ts, vals, sid, valid, counter_max, reset_value,
                      counter=counter, drop_resets=drop_resets)


# ---------------------------------------------------------------------------
# Union-grid group aggregation with interpolation (reference-parity path)
# ---------------------------------------------------------------------------

@jit_plan(ExecPlan(name="grid.contributions", axis="series",
                   static_argnames=("interp",)))
def series_contributions(ts: jnp.ndarray, vals: jnp.ndarray,
                         counts: jnp.ndarray, grid: jnp.ndarray, *,
                         interp: str = "lerp"):
    """Each series' contribution at every grid point.

    ts/vals are [S, T] left-aligned padded rows; grid is [G] sorted. A
    series contributes its exact value at its own timestamps, an
    interpolation ('lerp' or 'step' last-value-hold) between them, and
    nothing outside [first, last]. Returns (contrib [S, G], cmask [S, G]).
    """
    T = ts.shape[1]
    idx = jnp.arange(T)
    big = jnp.int32(2**31 - 1)

    def one_series(row_ts, row_vals, n):
        # Padded slots read as +inf-alike; searchsorted-right gives the
        # count of points <= x.
        safe_ts = jnp.where(idx < n, row_ts, big)
        pos = jnp.searchsorted(safe_ts, grid, side="right")
        has_prev = pos > 0
        i0 = jnp.clip(pos - 1, 0, T - 1)
        i1 = jnp.clip(pos, 0, T - 1)
        x0 = safe_ts[i0]
        y0 = row_vals[i0]
        x1 = safe_ts[i1]
        y1 = row_vals[i1]
        exact = has_prev & (x0 == grid)
        in_range = has_prev & (pos < n) | exact  # first <= x <= last
        if interp == "lerp":
            dx = jnp.maximum((x1 - x0).astype(jnp.float32), 1e-9)
            t = (grid - x0).astype(jnp.float32) / dx
            interpd = y0 + t * (y1 - y0)
        elif interp == "step":
            interpd = y0
        elif interp == "none":
            # zimsum/mimmin/mimmax: only exact samples contribute.
            in_range = exact
            interpd = y0
        else:
            raise ValueError(f"unknown interp: {interp}")
        contrib = jnp.where(exact, y0, interpd)
        return jnp.where(in_range, contrib, 0.0), in_range

    return jax.vmap(one_series)(ts, vals, counts)

@jit_plan(ExecPlan(name="grid.union", axis="series"))
def union_grid(ts: jnp.ndarray, counts: jnp.ndarray):
    """Deduplicated sorted union of S padded timestamp rows.

    ts is [S, T] int32 left-aligned; counts [S]. Returns (grid [S*T]
    int32, gmask [S*T] bool) with real entries compacted to the front —
    the grid-construction half of group_interpolate, exposed separately
    so percentile queries build the grid once and feed it straight to
    series_contributions.
    """
    S, T = ts.shape
    idx = jnp.arange(T)
    row_valid = idx[None, :] < counts[:, None]
    big = jnp.int32(2**31 - 1)
    flat = jnp.where(row_valid, ts, big).reshape(-1)
    sorted_ts = jnp.sort(flat)
    first = jnp.concatenate([
        jnp.array([True]), sorted_ts[1:] != sorted_ts[:-1]])
    gmask = first & (sorted_ts != big)
    order = jnp.argsort(~gmask, stable=True)
    return sorted_ts[order], gmask[order]


@jit_plan(ExecPlan(name="grid.group_interpolate", axis="series",
                   static_argnames=("agg", "interp")))
def group_interpolate(ts: jnp.ndarray, vals: jnp.ndarray,
                      counts: jnp.ndarray, *, agg: str,
                      interp: str = "lerp"):
    """Aggregate S padded series on the union of their timestamps.

    Args:
      ts:     [S, T] int32, each row sorted, left-aligned (valid prefix).
      vals:   [S, T] float32.
      counts: [S] int32 valid-point counts per row.
      interp: 'lerp' or 'step' (last-value hold, for rates).

    Returns (grid [G=S*T] int32, out [G] float32, gmask [G] bool): the
    deduplicated union grid (padded; gmask marks real entries) and the
    aggregate at each grid point. A series contributes exact values at its
    own timestamps, interpolation elsewhere, nothing outside its
    [first, last] — reference SGIterator semantics (SpanGroup.java:370-796).
    """
    grid, gmask = union_grid(ts, counts)
    contrib, cmask = series_contributions(ts, vals, counts, grid,
                                          interp=interp)  # [S, G]

    cnt = cmask.astype(jnp.float32).sum(axis=0)
    v = jnp.where(cmask, contrib, 0.0)
    total = v.sum(axis=0)
    mean = total / jnp.maximum(cnt, 1.0)
    centered = jnp.where(cmask, contrib - mean[None, :], 0.0)
    m2 = (centered * centered).sum(axis=0)
    mn = jnp.where(cmask, contrib, _POS_INF).min(axis=0)
    mx = jnp.where(cmask, contrib, _NEG_INF).max(axis=0)
    out = _finish(agg, cnt, total, m2, mn, mx)
    gmask = gmask & (cnt > 0)
    return grid, out, gmask
