#!/usr/bin/env python
"""The per-(series, bucket) stage alone, on the chip: what PERF.md §6
(PR 46) reads its stage-alone table from.

A seeded stream of 20,971,520 slots (13 h of 4,096 series at 10 s, the
rest padding, as the quarter-octave ladder pads a fleet-wide 12 h
request of cpu4k-13h.hist-12h) in hourly, 5-min and 1-min runs and in no
order, through ``downsample_multigroup`` (the raw plan's fleet-wide
program), eight series' 40,960 slots through the same (a narrow
request's stage), and 425 decoded blocks of 43,008 points through
``slab_stage_rows`` (the fused plan's dense leg at K = 512). One JSON
line a case: the median and the best of five calls after the first, the
first call's excess over the median (the compile), the updates the
scatters were handed where the tree under test reports them, and a sum
and a digest of the answer's bits to hold two trees to each other
(``moved``: the digest of the same points laid 77 slots further on,
which is the first's where a run's sum does not follow where it lies).

It imports the package of the directory it is run from, so two trees
are compared in one call on one chip:

    python3 scripts/stage_alone.py --seed 7 --label change
    (cd _archive/parent && PYTHONPATH=. python3 ../../scripts/stage_alone.py \\
        --seed 7 --label parent)

``--block N ...`` runs every case again with ``kernels._STAGE_BLOCK``
set to N (a tree without it ignores that). ``--small`` is a rehearsal
on the CPU at a sixty-fourth of the size: its times are no speed.
"""

import argparse
import hashlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from opentsdb_tpu.compress import kernels as ck  # noqa: E402
from opentsdb_tpu.ops import kernels  # noqa: E402

# (case, order, interval, buckets, downsample aggregator)
CASES = [("hourly", "runs", 3600, 16, "avg"),
         ("hourly", "runs", 3600, 16, "max"),
         ("5min", "runs", 300, 256, "max"),
         ("1min", "runs", 60, 1024, "max"),
         ("none", "none", 3600, 16, "avg")]
HOURS, P_BLK, R_BLK = 13, 43008, 128


def stream(seed, order, slots, series):
    """(ts, vals, sid, valid): HOURS hours of ``series`` series at 10 s
    laid hour by hour, a series-hour's 360 points in a row (or all of
    them shuffled), padded to ``slots`` with invalid slots."""
    rng = np.random.default_rng(seed)
    n = HOURS * series * 360
    i = np.arange(n)
    run = i // 360
    sid = (run % series).astype(np.int32)
    ts = ((run // series) * 3600 + (i % 360) * 10).astype(np.int32)
    vals = rng.normal(50, 10, n).astype(np.float32)
    if order == "none":
        p = rng.permutation(n)
        sid, ts, vals = sid[p], ts[p], vals[p]
    pad = slots - n
    return (np.pad(ts, (0, pad)), np.pad(vals, (0, pad)),
            np.pad(sid, (0, pad)), np.arange(slots) < n)


def digest(out) -> str:
    """The answer's bits: a series' bucket values where it has any."""
    values = np.where(np.asarray(out["series_mask"]),
                      np.asarray(out["series_values"]), np.float32(0))
    return hashlib.sha1(values.tobytes()).hexdigest()[:12]


def timed(f, reps=5):
    t0 = time.perf_counter()
    out = jax.block_until_ready(f())
    first = time.perf_counter() - t0
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(f())
        ts.append(time.perf_counter() - t0)
    return first, sorted(ts)[len(ts) // 2], min(ts), out


def raw_cases(emit, seed, slots, series, cases=CASES):
    held = {}
    gmap = jax.device_put(np.arange(series, dtype=np.int32))
    for name, order, interval, buckets, agg in cases:
        if order not in held:
            held.clear()
            held[order] = jax.block_until_ready(
                [jax.device_put(a)
                 for a in stream(seed, order, slots, series)])
        cols = held[order]

        def f(cols=cols):
            return kernels.downsample_multigroup(
                *cols, gmap, num_series=series, num_groups=series,
                num_buckets=buckets, interval=interval, agg_down=agg,
                agg_group=agg)
        first, med, best, out = timed(f)
        # The valid slots are the stream's first: 77 further on, the
        # padding's place taken.
        moved = f([jnp.roll(c, 77) for c in cols])
        emit(program="downsample.multigroup", case=name, agg=agg,
             slots=slots, compile_s=round(first - med, 3),
             ms=round(med * 1e3, 3), best_ms=round(best * 1e3, 3),
             handed=int(out["handed"]) if "handed" in out else None,
             digest=digest(out), moved=digest(moved),
             check=float(jnp.sum(jnp.where(out["group_mask"],
                                           out["group_values"], 0.0))))


def dense_case(emit, seed, series, rows, k, k_real, slots):
    """``k_real`` of ``rows`` cached blocks, each 118 records of one
    series-hour, through the dense leg padded to ``k`` rows; and the
    same points packed record after record into a stream of ``slots``
    through the raw plan's program (``packed``: its digest, the dense
    leg's where the two plans' sums are one)."""
    rng = np.random.default_rng(seed + 4)
    col = np.arange(P_BLK)
    qd = np.where(col < 118 * 360, (col % 360) * 10, 0).astype(np.int32)
    slab_qd = jax.device_put(np.broadcast_to(qd, (rows, P_BLK)).copy())
    values = rng.normal(50, 10, (rows, P_BLK)).astype(np.float32)
    slab_vals = jax.device_put(values)
    held = np.zeros(k, np.int32)
    held[:k_real] = rng.permutation(rows)[:k_real]
    rec = np.arange(k * R_BLK).reshape(k, R_BLK)
    live = (rec % R_BLK < 118) & (rec // R_BLK < k_real)
    npts = np.where(rec % R_BLK < 118, 360, 0).astype(np.int32)
    starts = np.cumsum(npts, axis=1, dtype=np.int32) - npts
    run = (rec // R_BLK) * 118 + rec % R_BLK
    sid = np.where(live, run % series, 0).astype(np.int32)
    rel_base = np.where(live, (run // series) * 3600, 0).astype(np.int32)
    args = [jax.device_put(a) for a in (held, starts, rel_base, sid, live)]
    scal = (np.int32(0), np.int32(HOURS * 3600), np.int32(0),
            np.float32(0), np.float32(0))
    n = k_real * 118 * 360
    i = np.arange(n)
    packed = [np.pad(a, (0, slots - n)) for a in (
        ((i // 360 // series) * 3600 + i % 360 * 10).astype(np.int32),
        values[held[:k_real], :118 * 360].reshape(-1),
        (i // 360 % series).astype(np.int32))] + [np.arange(slots) < n]
    for agg in ("avg", "max"):
        def f():
            return ck.slab_stage_rows(slab_qd, slab_vals, *args, *scal,
                                      num_series=series, num_buckets=16,
                                      interval=3600, agg_down=agg)
        first, med, best, out = timed(f)
        raw = kernels.downsample_multigroup(
            *packed, np.arange(series, dtype=np.int32), num_series=series,
            num_groups=series, num_buckets=16, interval=3600, agg_down=agg,
            agg_group=agg)
        emit(program="compress.devcache_stage", case=f"dense-k{k}", agg=agg,
             slots=k * P_BLK, compile_s=round(first - med, 3),
             ms=round(med * 1e3, 3), best_ms=round(best * 1e3, 3),
             handed=int(out[5]) if len(out) > 5 else None,
             digest=digest({"series_values": out[0], "series_mask": out[1]}),
             packed=digest(raw),
             check=float(jnp.sum(jnp.where(out[1], out[0], 0.0))))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--label", default="change")
    ap.add_argument("--block", type=int, nargs="*", default=[])
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        sys.exit(f"{dev.platform}: the full size is for the chip (--small)")
    slots, series = (5 << 16, 64) if args.small else (5 << 22, 4096)
    dense = (20, 16, 7) if args.small else ((1 << 26) // P_BLK, 512, 425)
    for block in [None] + args.block:
        if block is not None:
            jax.clear_caches()
            kernels._STAGE_BLOCK = block

        def emit(**kw):
            print(json.dumps(dict(label=args.label, seed=args.seed,
                                  device=dev.device_kind, block=block,
                                  **kw)), flush=True)
        raw_cases(emit, args.seed, slots, series)
        # What a narrow request hands the stage: eight series' 13 h.
        raw_cases(emit, args.seed, 5 << 13, 8, CASES[:2])
        dense_case(emit, args.seed, series, *dense, slots)


if __name__ == "__main__":
    main()
