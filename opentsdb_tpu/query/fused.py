"""The fused plan: a request past the window, served off TSST4 blocks
by decode-plus-aggregate on the device (compress/). What follows a
stage is query/grid.py's."""

from __future__ import annotations

import numpy as np

from opentsdb_tpu.compress import fused as _fused
from opentsdb_tpu.compress import kernels as _ckernels
from opentsdb_tpu.compress.devcache import pad_fine as _pad_fine
from opentsdb_tpu.core import codec
from opentsdb_tpu.core.const import MAX_TIMESPAN
from opentsdb_tpu.core.errors import NoSuchUniqueName
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.query import grid as qgrid
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.query.grid import (IMAX, IMIN, _filter_key, _GridGroups,
                                     _pad_size, clamp32)
from opentsdb_tpu.utils.lru import LRUCache

# Fused decode-plus-aggregate serving off TSST4 blocks (compress/):
# wall time of the gather + kernel dispatch per served query.
_M_FUSED = _metrics.timer("compress.fused_agg")

# Fused coverage accounting: attempts = queries past the fused gates
# (the fused-eligible battery), served = answered plan:"fused"; the
# gauge is their ratio, what /stats and /metrics expose. Every decline
# between the two increments compress.fused.decline{reason=} — the
# no-silent-declines contract is these three instruments agreeing.
_C_FUSED_ATTEMPT = _metrics.counter("compress.fused.attempt")
_C_FUSED_SERVED = _metrics.counter("compress.fused.served")
# What the gathers of the fused plan met: the points of the blocks they
# touched, of those the points of matching in-range records, the
# blocks' payload bytes (the larger stream of each), and the bytes the
# byte-stream leg sent to the device (the block cache counts
# its own fills: compress.devcache.uploaded_bytes).
_C_FUSED_POINTS = _metrics.counter("compress.fused.points")
_C_FUSED_MATCHED = _metrics.counter("compress.fused.matched_points")
_C_FUSED_PAYLOAD = _metrics.counter("compress.fused.payload_bytes")
_C_FUSED_UPLOADED = _metrics.counter("compress.fused.uploaded_bytes")
_metrics.gauge(
    "compress.fused.coverage",
    lambda: (_C_FUSED_SERVED.value / _C_FUSED_ATTEMPT.value
             if _C_FUSED_ATTEMPT.value else 0.0))


def _count_decline(reason: str) -> None:
    _metrics.counter("compress.fused.decline", {"reason": reason}).inc()


class FusedPlan:
    """``serve`` answers a downsampled query straight from TSST4
    compressed blocks: one fused decode-plus-aggregate XLA program
    produces the per-(series, bucket) stage grids (the decoded columns
    are never materialized on host), then the SAME apply kernels the
    device-resident window uses finish grouping/percentiles.
    Exact or None (the fall-back contract): any memtable-resident
    data in range, non-v4 generation, non-TSF32 block, overlay
    risk, or int32 overflow declines to the scan path. The plan owns
    its caches, its counters and its fused.* spans."""

    label = "fused"
    storage_free = False    # reads blocks: after the rollup tiers

    def __init__(self, tsdb, backend: str, mesh, tag_filters,
                 series_hint) -> None:
        self.tsdb = tsdb
        self.backend = backend
        self.mesh = mesh
        # The executor's: a tag-filter map -> UID-level (exact,
        # group_bys); (metric, exact, group_bys) -> the selector's
        # known series (``series_keys``).
        self._tag_filters = tag_filters
        self._series_hint = series_hint
        # Device grids keyed by the generation set + range + downsample
        # plan. Entries pin their source SSTable objects so id() reuse
        # can't alias a dropped generation; eligibility (dirty range,
        # format mix) is re-checked per query — only the decode+stage
        # compute caches.
        self.stage_cache = LRUCache(4)
        # What a gather would else work out anew: the selector's
        # verdict a series key, by (metric, filter), and a series' tags
        # by name.
        self.sel_memo = LRUCache(64)
        self.named: dict[bytes, dict[str, str]] = {}
        # A gather's groups as its answer takes them (_GridGroups, the
        # labels kept), by what the groups are a function of: (metric,
        # filter) as the key, the gather's series directory in the
        # value, as the generation is in the resident plan's.
        self.plan_cache = LRUCache(64)
        # Device-side decoded-block cache (compress/devcache.py):
        # per-block query-independent columns stay resident on device,
        # bounded by total cached points. Keyed by SSTable OBJECT +
        # block index (entries pin their generation against id reuse).
        dbp = int(tsdb.config.devblock_points)
        self.devcache = None
        if dbp > 0 and backend != "cpu":
            from opentsdb_tpu.compress.devcache import DeviceBlockCache
            self.devcache = DeviceBlockCache(dbp)

    def serve(self, spec, start: int, end: int, agg):
        tsdb = self.tsdb
        cfg = tsdb.config
        if (self.backend == "cpu"
                or not spec.downsample
                or agg.kind not in ("moment", "percentile")
                or Aggregators.get(spec.downsample[1]).kind != "moment"
                or not cfg.sstable_fused_agg):
            return None
        store = tsdb.store
        if getattr(store, "encoded_range", None) is None \
                or getattr(store, "chunk_state", None) is None:
            return None
        interval, dsagg = spec.downsample
        if start < 0 or end > 0xFFFFFFFF \
                or end - start > IMAX - 4 * MAX_TIMESPAN:
            return None
        qbase = start - start % interval
        if end - qbase > IMAX:
            return None
        try:
            metric_uid = tsdb.metrics.get_id(spec.metric)
            exact, group_bys = self._tag_filters(spec.tags)
        except NoSuchUniqueName:
            return None  # scan path raises the canonical error
        b_lo = codec.base_time(start)
        b_hi = min(codec.base_time(end), 0xFFFFFFFF)
        _C_FUSED_ATTEMPT.inc()
        # Memtable-resident (dirty) data in range: decline — a frozen
        # answer must equal the scan bit-for-bit, and overlaying live
        # rows is the scan path's job.
        seqs, floors, stamps, dirty = store.chunk_state(
            tsdb.table, b_lo, b_hi + MAX_TIMESPAN)
        if dirty:
            _count_decline("dirty")
            return None
        with _M_FUSED.time():
            res = self._serve_inner(
                spec, start, end, agg, metric_uid, exact, group_bys,
                interval, dsagg, qbase, b_lo, b_hi)
        if res is not None:
            _C_FUSED_SERVED.inc()
        return res

    def _serve_inner(self, spec, start, end, agg, metric_uid,
                     exact, group_bys, interval, dsagg, qbase,
                     b_lo, b_hi):
        """The fused plan past its gates, as five spans under
        planner.pick (README, "Observability"): fused.gather (which
        blocks, their records, the groups), fused.dispatch (the
        uploads and the calls of the stage and apply programs),
        fused.wait (traced requests only, as aggregate.wait),
        fused.fetch, fused.results."""
        tsdb = self.tsdb
        rate_kw = qgrid.rate_kw(spec)
        fk = _filter_key(exact, group_bys)
        # The tag filter is part of the stage's identity now that it's
        # pushed into the gather (filtered-out series never reach the
        # stage grid) — leaving it out would serve one filter's grid
        # under another's key.
        skey_cache = (metric_uid, b_lo, b_hi, interval, dsagg, start,
                      end, fk, tuple(sorted(rate_kw.items())))
        hit = self.stage_cache.get(skey_cache)
        if hit is not None:
            gens_hit, src_keys, epoch, stage, groups = hit
            # Validate against the CURRENT generation set: gens_hit
            # holds the SSTable objects the cached stage was computed
            # from (object identity — the entry pins them, so id
            # recycling cannot alias a dropped generation). Any
            # checkpoint/compaction swap mismatches and rebuilds.
            spans = tsdb.store.encoded_range(
                tsdb.table, metric_uid + b_lo.to_bytes(4, "big"),
                metric_uid + min(b_hi + MAX_TIMESPAN,
                                 0xFFFFFFFF).to_bytes(4, "big"))
            if spans is None or \
                    len(spans) != len(gens_hit) or \
                    any(g is not h for (g, _, _), h
                        in zip(spans, gens_hit)):
                hit = None
                self.stage_cache.pop(skey_cache)
        src = None
        if hit is None:
            with obs_trace.span("fused.gather") as sp:
                memo = self.sel_memo.get((metric_uid, fk))
                if memo is None:
                    memo = {}
                    self.sel_memo.put((metric_uid, fk), memo)
                try:
                    src = _fused.gather(
                        tsdb.store, tsdb.table, metric_uid, b_lo, b_hi,
                        selector=qgrid.series_selector(exact, group_bys),
                        series_keys=self._series_hint(
                            metric_uid, exact, group_bys).get(
                                "series_keys"),
                        sel_memo=memo)
                except _fused.Decline as d:
                    _count_decline(d.reason)
                    return None
                if sp is not None:
                    dc = self.devcache
                    sp.tags.update(
                        blocks=len(src.blocks), points=src.npoints,
                        matched=src.matched,
                        series=len(src.series_keys),
                        payload_bytes=src.payload_bytes(),
                        cached=dc.held(src) if dc is not None else 0)
            if src.npoints == 0:
                return []
            _C_FUSED_POINTS.inc(src.npoints)
            _C_FUSED_MATCHED.inc(src.matched)
            _C_FUSED_PAYLOAD.inc(src.payload_bytes())
            epoch = src.epoch
            src_keys = src.series_keys
            groups = src.groups
        if not groups:
            return []
        S_pad = _pad_size(len(src_keys))
        if not IMIN <= qbase - epoch <= IMAX:
            _count_decline("int32-span")
            return None
        b_live = int((end - qbase) // interval + 1)
        num_buckets = _pad_size(b_live)
        if S_pad * num_buckets >= 2**31:
            _count_decline("grid-too-large")
            return None
        grid = self.plan_cache.get((metric_uid, fk))
        if grid is None or grid.series_keys != src_keys:
            # New to this executor, or the store has gained or lost a
            # series of the range since: the groups' sids are positions
            # in the gather's directory, so the kept labels go with it.
            grid = _GridGroups(groups, src_keys)
            self.plan_cache.put((metric_uid, fk), grid)
        ngroups, shrink = qgrid.clip(grid, num_buckets, b_live,
                                     tsdb.config.wire_bf16)
        with obs_trace.span("fused.dispatch") as sp:
            if src is not None:
                try:
                    stage, leg = self._stage(
                        src, S_pad, num_buckets, interval, dsagg,
                        rate_kw,
                        clamp32(start - epoch), clamp32(end - epoch),
                        np.int32(qbase - epoch))
                except _fused.Decline as d:
                    _count_decline(d.reason)
                    return None
                # Key the entry on the SNAPSHOT the stage was actually
                # computed from (src.spans — not a fresh encoded_range,
                # which a checkpoint racing this query could have moved
                # past the gathered data). The held objects both pin
                # against id reuse and make hit-validation pure identity.
                self.stage_cache.put(
                    skey_cache,
                    (tuple(g for g, _, _ in src.spans),
                     src_keys, epoch, stage, groups))
            else:
                leg = "cached"
            gv, gm = qgrid.apply(stage, *qgrid.group_masks(grid, S_pad),
                                 agg, spec.aggregator, ngroups, shrink)
            if sp is not None:
                sp.tags["leg"] = leg
        gv, gm = qgrid.fetch("fused", gv, gm, stage)
        named = self.named
        if len(named) > 1 << 20:
            named.clear()

        def tags_of(sid: int) -> dict[str, str]:
            sk = src_keys[sid]
            tags = named.get(sk)
            if tags is None:
                tags = named[sk] = qgrid.named_tags(tsdb, sk)
            return tags

        return qgrid.results("fused", spec.metric, grid, tags_of, stage,
                             gv, gm, shrink["b_out"], interval, qbase)

    def _stage(self, src, S_pad, num_buckets, interval, dsagg,
               rate_kw, lo32, hi32, shift32):
        """Dispatch the window stage of one gather; returns (the stage
        contract as a list with a slot for the fetched presence, the
        leg that ran). On one device the gather's blocks are decoded
        into the block cache's slabs (misses only) and the stage reads
        them: per matched point where the selector keeps under half of
        the points of the blocks it touches (``sel``), else per whole
        block (``rows``); without the cache, or for a gather its slabs
        cannot hold, the plan declines (``cache-off``, ``oversize``)
        and the raw plan serves. Across a mesh the byte-stream leg
        decodes and stages in one program (``mesh``)."""
        statics = dict(
            num_series=S_pad, num_buckets=num_buckets,
            interval=interval, agg_down=dsagg, rate=rate_kw["rate"],
            counter=rate_kw["counter"],
            drop_resets=rate_kw["drop_resets"])
        scalars = (lo32, hi32, shift32,
                   np.float32(rate_kw["counter_max"]),
                   np.float32(rate_kw["reset_value"]))

        def counted(out, slots):
            *grids, handed = out
            qgrid._stage_handed(handed, slots)
            return grids + [None]

        if self.mesh is None:
            dc = self.devcache
            if dc is None:
                raise _fused.Decline("cache-off")
            selective = 2 * src.matched <= src.npoints

            def run(qd, vals, slots):
                inputs = (dc.point_inputs if selective
                          else dc.record_inputs)(src, slots, S_pad)
                # The stream: a matched point each, or every point of
                # the rows gathered.
                return counted(
                    (_ckernels.slab_stage_sel if selective
                     else _ckernels.slab_stage_rows)(
                        qd, vals, *inputs, *scalars, **statics),
                    len(inputs[0]) * (1 if selective else dc.P_BLK))

            stage = dc.stage(src, run)
            if stage is None:
                raise _fused.Decline("oversize")
            return stage, "sel" if selective else "rows"
        # The plane's pjit-preferred leg: the point stream (whole
        # compressed blocks) shards over the mesh, payloads and the
        # [S, B] outputs replicate (compress/kernels.py
        # FUSED_STAGE_PLAN). Shapes that don't divide the mesh run the
        # single-device compile — counted (mesh-indivisible) but still
        # served fused, never a fallback to the scan.
        ps = src.point_stream()
        npoints = len(ps.valid)
        _C_FUSED_UPLOADED.inc(
            14 * npoints + len(ps.ts_pay) + len(ps.v_pay))
        P_pad = _pad_fine(npoints)

        def pad(a, dtype, fill=0):
            out = np.full(P_pad, fill, dtype)
            out[:len(a)] = a
            return out

        def padbuf(a):
            # Payload bytes pad pow2: decode compute is per-POINT,
            # byte padding costs only upload, and one compile class
            # per octave keeps shifted windows from recompiling on
            # byte-length wobble.
            n = max(len(a), 1)
            out = np.zeros(1 << (n - 1).bit_length(), np.uint8)
            out[:len(a)] = a
            return out

        args = (pad(ps.ts_nb, np.int32), padbuf(ps.ts_pay),
                pad(ps.v_nb, np.int32), padbuf(ps.v_pay),
                pad(ps.first_idx, np.int32),
                pad(ps.blk_first, np.int32),
                pad(ps.rel_base_pt, np.int32),
                pad(np.minimum(ps.sid_pt, S_pad - 1), np.int32),
                pad(ps.valid, bool, False))
        if P_pad % int(self.mesh.devices.size) == 0:
            fused_fn = _ckernels.fused_block_stage_mesh(
                self.mesh, vkind=src.kind, **statics)
            return counted(fused_fn(*args, *scalars), P_pad), "mesh"
        _count_decline("mesh-indivisible")
        out = _ckernels.fused_block_stage(
            *args, *scalars[:3], **statics, vkind=src.kind,
            counter_max=rate_kw["counter_max"],
            reset_value=rate_kw["reset_value"])
        return counted(out, P_pad), "bytes"
