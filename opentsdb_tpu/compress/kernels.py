"""Batched JAX decode + fused decode-plus-aggregate over TSST4 blocks.

The shape of the win (PAPERS.md "GPU Acceleration of SQL Analytics on
Compressed Data", arxiv 2506.10092): keep the scan compressed and run
the reduction ON the encoded form. ``fused_block_stage`` is one XLA
program that takes the blocks' packed control/payload byte streams and
produces the per-(series, bucket) downsample grids the query pipeline
consumes (ops/kernels._window_series_stage — the SAME stage the
device-resident window uses, so group aggregation, percentiles, rate
and gap-fill semantics are shared, not re-implemented). The decoded
timestamp/value columns exist only as intermediates inside the
program: nothing N-sized is ever materialized to host memory.

Decode steps, all vectorized:
- variable-width payload gather: 4 static byte gathers assembled by
  shift/or, masked by the per-point nibble byte count;
- zigzag undo; two segmented cumsums rebuild qualifier deltas from
  the delta-of-delta entries (global cumsum minus a gather at each
  record's first entry — int32 wraparound keeps in-segment differences
  exact even when the global running sum overflows);
- value inverse by block codec (the ``vkind`` static):
  * TSF32: XOR undo via an associative scan, re-based per block (the
    encoder chains xors from 0 at each block start), bitcast to f32;
  * TSINT: zigzag undo + ONE segmented cumsum over the per-block
    delta chain (the encoder chains int deltas from 0 at each block
    start, the additive mirror of the XOR rebase). Eligibility
    (compress/fused.py) has verified every decoded value fits int32,
    so the modular cumsum is exact and the f32 cast matches the scan
    path's own kernel-entry cast bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from opentsdb_tpu.ops.kernels import _window_series_stage
from opentsdb_tpu.parallel.compile import compile_with_plan
from opentsdb_tpu.parallel.mesh import SERIES_AXIS
from opentsdb_tpu.parallel.plan import ExecPlan


def _varbytes_u32(pay: jnp.ndarray, nb: jnp.ndarray) -> jnp.ndarray:
    """[P] uint32 values from a packed payload: ``nb`` significant
    big-endian bytes per value, concatenated. nb == 0 -> 0."""
    off = jnp.cumsum(nb) - nb   # exclusive prefix
    out = jnp.zeros(nb.shape, jnp.uint32)
    limit = pay.shape[0] - 1 if pay.shape[0] else 0
    for j in range(4):
        m = j < nb
        idx = jnp.clip(off + j, 0, limit)
        byte = pay[idx].astype(jnp.uint32)
        shift = (jnp.where(m, nb - 1 - j, 0) * 8).astype(jnp.uint32)
        out = out | jnp.where(m, byte << shift, jnp.uint32(0))
    return out


def _unzigzag32(z: jnp.ndarray) -> jnp.ndarray:
    half = (z >> jnp.uint32(1)).astype(jnp.int32)
    return half ^ -((z & jnp.uint32(1)).astype(jnp.int32))


def _seg_cumsum(x: jnp.ndarray, first_idx: jnp.ndarray) -> jnp.ndarray:
    """Inclusive per-segment cumsum: c[i] - c[first-1]. int32
    wraparound is deliberate (see module docstring)."""
    c = jnp.cumsum(x)
    cp = jnp.concatenate([jnp.zeros(1, x.dtype), c])
    return c - cp[first_idx]


def decode_points(ts_nb, ts_pay, v_nb, v_pay, first_idx, blk_first,
                  rel_base, *, vkind="f32"):
    """(rel_ts int32, values float32) for the concatenated point
    stream — the batched decode kernel shared by the fused stage and
    the standalone jitted decoder. ``vkind`` selects the value
    inverse: "f32" (TSF32 XOR chain) or "int" (TSINT delta chain)."""
    ent = _unzigzag32(_varbytes_u32(ts_pay, ts_nb))
    steps = _seg_cumsum(ent, first_idx)
    deltas = _seg_cumsum(steps, first_idx)
    rel_ts = rel_base + deltas
    x = _varbytes_u32(v_pay, v_nb)
    if vkind == "int":
        vals = _seg_cumsum(_unzigzag32(x), blk_first) \
            .astype(jnp.float32)
    else:
        X = jax.lax.associative_scan(jnp.bitwise_xor, x)
        Xp = jnp.concatenate([jnp.zeros(1, jnp.uint32), X])
        bits = X ^ Xp[blk_first]
        vals = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return rel_ts, vals


decode_points_jit = compile_with_plan(
    decode_points, ExecPlan(name="compress.decode_points", axis="block",
                            static_argnames=("vkind",)))

_FUSED_STATICS = ("num_series", "num_buckets", "interval", "agg_down",
                  "rate", "counter", "drop_resets", "vkind")

# The fused stage's mesh leg is the plane's pjit-preferred style: the
# point stream (the concatenation of whole compressed blocks) shards
# over the mesh while the payload byte streams and scalars replicate;
# the [S, B] stage grids come back replicated. The body stays the
# global-view program below — GSPMD partitions the segment reductions
# and scans and inserts the collectives, which is exactly why the
# plan prefers pjit when explicit shardings exist (SNIPPETS.md's
# Titanax compile_step_with_plan shape). Answers carry the fused
# path's existing f32-tolerance contract (partial-sum order changes).
FUSED_STAGE_PLAN = ExecPlan(
    name="compress.fused_stage", axis="block", style="pjit",
    static_argnames=_FUSED_STATICS,
    in_specs=(P(SERIES_AXIS), P(), P(SERIES_AXIS), P(),
              P(SERIES_AXIS), P(SERIES_AXIS), P(SERIES_AXIS),
              P(SERIES_AXIS), P(SERIES_AXIS), P(), P(), P(),
              P(), P()),
    out_specs=(P(), P(), P(), P(), P(), P()))


def _fused_block_stage_ops(ts_nb, ts_pay, v_nb, v_pay, first_idx,
                           blk_first, rel_base, sid, valid, lo, hi,
                           shift, counter_max, reset_value, *,
                           num_series, num_buckets, interval,
                           agg_down, rate=False, counter=False,
                           drop_resets=False, vkind="f32"):
    """All-positional face of the fused stage for the pjit mesh leg
    (pjit rejects call-time kwargs once shardings are specified).
    counter_max/reset_value ride as replicated scalar OPERANDS — they
    are client-controlled query params, and baking them static would
    let one hostile dashboard mint a fresh XLA compile per request."""
    return _fused_block_stage(
        ts_nb, ts_pay, v_nb, v_pay, first_idx, blk_first, rel_base,
        sid, valid, lo, hi, shift, num_series=num_series,
        num_buckets=num_buckets, interval=interval, agg_down=agg_down,
        rate=rate, counter_max=counter_max, reset_value=reset_value,
        counter=counter, drop_resets=drop_resets, vkind=vkind)


def fused_block_stage_mesh(mesh, **statics):
    """The fused stage compiled for ``mesh`` with the SHAPE statics
    pre-bound; takes the 12 point-stream args + (counter_max,
    reset_value) positionally. The executor asks per dispatch; the
    plane's cache answers."""
    st = tuple(sorted(statics.items()))
    return compile_with_plan(_fused_block_stage_ops, FUSED_STAGE_PLAN,
                             mesh, statics=st)


def _fused_block_stage(ts_nb, ts_pay, v_nb, v_pay, first_idx, blk_first,
                      rel_base, sid, valid, lo, hi, shift, *,
                      num_series, num_buckets, interval, agg_down,
                      rate=False, counter_max=0.0, reset_value=0.0,
                      counter=False, drop_resets=False, vkind="f32"):
    """Decode + range-mask + per-series downsample in ONE program.

    Inputs are per-point arrays (padded to a static size; padding has
    valid=False and nb=0): nibble byte counts + payload byte streams
    for timestamps and values, each point's record-first index and
    block-first index, the record's base time relative to the query
    epoch, and the series id. Returns the window-stage contract
    (series_values, series_mask, filled, in_range, presence) that
    ops.kernels.window_moment_apply / window_quantile_apply consume —
    so every group aggregator, percentile and rate the resident-window
    path serves, this path serves identically — and after it the
    stage's count of scatter updates (ops.kernels._window_series_stage).
    """
    rel_ts, vals = decode_points(ts_nb, ts_pay, v_nb, v_pay,
                                 first_idx, blk_first, rel_base,
                                 vkind=vkind)
    return _window_series_stage(
        rel_ts, vals, sid, valid, lo, hi, shift,
        num_series=num_series, num_buckets=num_buckets,
        interval=interval, agg_down=agg_down, rate=rate,
        counter_max=counter_max, reset_value=reset_value,
        counter=counter, drop_resets=drop_resets)


fused_block_stage = compile_with_plan(
    _fused_block_stage,
    ExecPlan(name="compress.fused_stage", axis="block",
             static_argnames=_FUSED_STATICS))


# -- device block cache legs ------------------------------------------------
#
# The devcache (compress/devcache.py) keeps the QUERY-INDEPENDENT
# decoded columns of single blocks resident on device, a block a row
# of two [slots, P_BLK] slabs: per-point qualifier deltas and decoded
# f32 values. A miss uploads the block's streams as the file holds
# them (packed nibbles, payload bytes, per-record point counts) and
# decodes them into its row; a query then uploads per-RECORD arrays
# for whole blocks (the dense leg) or per-POINT arrays for its matched
# points alone (the selective leg) and runs the same window stage.
# Answers are bit-identical to the byte-stream fused program:
# identical decode math (the XOR/delta chains never cross block
# boundaries), identical point order, and padding points belong to a
# pad record every query marks invalid. A row of P_BLK slots lays a
# record over other tiles and blocks of the stage than a packed stream
# does, which the stage's sums do not see: a run's float32 sum follows
# from its points in their order alone (ops/kernels._run_fold).
#
# On the TPU a gather or a scatter costs ~10 ns an ELEMENT whatever it
# moves (the first fill program here spent 65 ms on 8 blocks: a
# searchsorted, eight byte gathers and three gathers by record, a
# third of a million elements each), while a scan along a row is
# nearly free. A scatter of RUNS costs by the update: the stage's two
# scatters took 0.19 s each over a fleet-wide gather's 22M points a
# slot an update, and take a sixteenth of the updates since PR 46
# (a series-hour's 360 points are one run; PERF.md §6). So nothing
# below gathers by point but the two payload
# reads of a fill: what a point takes from its record is spread by a
# scatter of the RECORDS' differences at their first points and a
# cumsum along the row (``_spread``), and the XOR chain is a
# log-step shift-and-xor.

def _nibbles(packed, n):
    """[B, n] int32 byte counts from [B, n // 2] packed nibbles (high
    nibble first, codecs._pack_nibbles)."""
    b = packed.astype(jnp.int32)
    return jnp.stack([b >> 4, b & 15], axis=-1).reshape(
        packed.shape[0], n)


def _row_varbytes_u32(pay, nb):
    """``_varbytes_u32`` a block a row: [B, P] values from [B, W]
    payload bytes, each row's bytes packed from its own column 0. One
    gather a point: of the four bytes at its offset as one big-endian
    word, shifted down to the ``nb`` (at most 4) it owns."""
    b = jnp.pad(pay.astype(jnp.uint32), ((0, 0), (0, 3)))
    w = pay.shape[1]
    word = ((b[:, :w] << 24) | (b[:, 1:w + 1] << 16)
            | (b[:, 2:w + 2] << 8) | b[:, 3:w + 3])
    off = jnp.clip(jnp.cumsum(nb, axis=1) - nb, 0, w - 1)
    got = jnp.take_along_axis(word, off, axis=1)
    shift = (jnp.where(nb > 0, 4 - nb, 0) * 8).astype(jnp.uint32)
    return jnp.where(nb > 0, got >> shift, jnp.uint32(0))


def _spread(per_rec, starts, width):
    """[B, width] from [B, R]: each point the value of its record,
    where record r's points begin at ``starts[:, r]`` (ascending and
    under ``width``: a row always ends in a padding point). The
    records' differences added at their first points, summed along
    the row: a record with no point adds its difference where the
    next one begins, so the sums telescope whatever the counts. The
    places ascend through the batch, which the scatter is told (it
    compiles in half a second so, in eight otherwise). int32
    wraparound is deliberate."""
    rows = per_rec.shape[0]
    prev = jnp.pad(per_rec[:, :-1], ((0, 0), (1, 0)))
    at = jnp.arange(rows, dtype=jnp.int32)[:, None] * width + starts
    return jnp.cumsum(jax.ops.segment_sum(
        (per_rec - prev).reshape(-1), at.reshape(-1),
        num_segments=rows * width,
        indices_are_sorted=True).reshape(rows, width), axis=1)


def _row_seg_cumsum(x, starts):
    """``_seg_cumsum`` a block a row: the inclusive cumsum of ``x``
    less what had run up before each point's record began."""
    c = jnp.cumsum(x, axis=1)
    before = jnp.take_along_axis(c - x, starts, axis=1)
    return c - _spread(before, starts, x.shape[1])


def _row_xor_scan(x):
    """Inclusive XOR prefix along each row, by doubling: log2(P)
    shift-and-xor passes. (lax.associative_scan's odd/even slicing
    takes the TPU's compiler 12 s to lower at this size, the 1-D
    programs above as long for each cumsum: the fill is 2-D and
    compiles in seconds.)"""
    k = 1
    while k < x.shape[1]:
        x = x ^ jnp.pad(x[:, :-k], ((0, 0), (k, 0)))
        k <<= 1
    return x


def _slab_fill(slab_qd, slab_vals, slots, ts_nib, ts_pay, v_nib, v_pay,
               npts, *, vkind="f32"):
    """Decode a batch of B blocks into their slab rows (donated, so in
    place): ``decode_points``' math a block a row, since no chain
    crosses a block. ``ts_pay`` / ``v_pay`` [B, 4 P] hold each block's
    payload from column 0; ``npts`` [B, R_BLK] is each block's points
    a record, zero past its last record: the first of those is the pad
    record, where a row's padding points begin (they decode to a zero
    delta and are invalid in every query). ``slots`` past the slab (a
    short batch's padding) are dropped."""
    P = slab_qd.shape[1]
    starts = jnp.cumsum(npts, axis=1) - npts
    ent = _unzigzag32(_row_varbytes_u32(ts_pay, _nibbles(ts_nib, P)))
    qd = _row_seg_cumsum(_row_seg_cumsum(ent, starts), starts)
    x = _row_varbytes_u32(v_pay, _nibbles(v_nib, P))
    if vkind == "int":
        vals = jnp.cumsum(_unzigzag32(x), axis=1).astype(jnp.float32)
    else:
        vals = jax.lax.bitcast_convert_type(_row_xor_scan(x),
                                            jnp.float32)
    return (slab_qd.at[slots].set(qd, mode="drop"),
            slab_vals.at[slots].set(vals, mode="drop"))


slab_fill = compile_with_plan(
    _slab_fill,
    ExecPlan(name="compress.devcache_fill", axis="block",
             static_argnames=("vkind",), donate_argnums=(0, 1)))

_DEV_STATICS = ("num_series", "num_buckets", "interval", "agg_down",
                "rate", "counter", "drop_resets")


def _slab_stage_rows(slab_qd, slab_vals, slots, starts, rel_base, sid,
                     valid, lo, hi, shift, counter_max, reset_value, *,
                     num_series, num_buckets, interval, agg_down,
                     rate=False, counter=False, drop_resets=False):
    """Window stage over whole cached blocks (the dense leg): gather
    the K rows of ``slots`` and spread the per-record uploads
    [K, R_BLK] over their points — no payload bytes, no decode.
    ``starts`` is where in its row each record's points begin; a
    row's padding points belong to the pad record, which is invalid,
    and a padding slot's records are all invalid."""
    P = slab_qd.shape[1]
    # The four point streams are made whole before the stage reads
    # them: fused into its scatters, the TPU's compiler takes 30 s over
    # the program where the parts take 5.
    rel_ts, vals, sid, valid = jax.lax.optimization_barrier((
        (_spread(rel_base, starts, P) + slab_qd[slots]).reshape(-1),
        slab_vals[slots].reshape(-1),
        _spread(sid, starts, P).reshape(-1),
        _spread(valid.astype(jnp.int32), starts, P).reshape(-1) > 0))
    return _window_series_stage(
        rel_ts, vals, sid, valid, lo, hi, shift,
        num_series=num_series, num_buckets=num_buckets,
        interval=interval, agg_down=agg_down, rate=rate,
        counter_max=counter_max, reset_value=reset_value,
        counter=counter, drop_resets=drop_resets)


slab_stage_rows = compile_with_plan(
    _slab_stage_rows,
    ExecPlan(name="compress.devcache_stage", axis="block",
             static_argnames=_DEV_STATICS))


def _slab_stage_sel(slab_qd, slab_vals, row, col, rel_base, sid, valid,
                    lo, hi, shift, counter_max, reset_value, *,
                    num_series, num_buckets, interval, agg_down,
                    rate=False, counter=False, drop_resets=False):
    """Window stage over the matched points alone (the selective leg):
    ``row`` / ``col`` [M] are each matched point's slab row and its
    place in the block (a gather of M elements: the slabs are not
    flattened, which would copy them), the other three its record's
    base time, series and validity, expanded on the host — stage cost
    scales with the match and not with the blocks touched, and the
    cached columns stay selector-independent. Kept points stay in
    stream order, so every per-(series, bucket) reduction sees the
    operands the dense leg would hand it."""
    return _window_series_stage(
        rel_base + slab_qd[row, col], slab_vals[row, col], sid, valid,
        lo, hi, shift,
        num_series=num_series, num_buckets=num_buckets,
        interval=interval, agg_down=agg_down, rate=rate,
        counter_max=counter_max, reset_value=reset_value,
        counter=counter, drop_resets=drop_resets)


slab_stage_sel = compile_with_plan(
    _slab_stage_sel,
    ExecPlan(name="compress.devcache_stage_sel", axis="block",
             static_argnames=_DEV_STATICS))
