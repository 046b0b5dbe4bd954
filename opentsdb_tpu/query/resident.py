"""The resident plan: a request the device-resident hot window
(storage/devstore.py, or its mesh-sharded twin storage/devshard.py)
exactly covers is served from it: no storage scan, no host->device
point upload. What follows a stage is query/grid.py's."""

from __future__ import annotations

import concurrent.futures

import jax
import numpy as np

from opentsdb_tpu.core.errors import NoSuchUniqueName
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.ops import kernels
from opentsdb_tpu.query import grid as qgrid
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.query.grid import (IMAX, IMIN, _filter_key, _GridGroups,
                                     _Handed, _is_device_oom, _pad_size,
                                     clamp32)
from opentsdb_tpu.utils.lru import LRUCache

# The resident plan's stage cache (ResidentPlan.stage_cache): a miss
# builds a stage, which is the device's whole cost of a resident sub-query;
# evicted = stages dropped by hand (a dead data version, a device OOM),
# not the LRU's own turnover at its cap.
_C_STAGE_HIT = _metrics.counter("devwindow.stage.hit")
_C_STAGE_MISS = _metrics.counter("devwindow.stage.miss")
_C_STAGE_EVICTED = _metrics.counter("devwindow.stage.evicted")
# What the stages built were handed, in slots of the resident chunks
# (padding included): visited = the blocks the zone maps let through to
# window.chunk_fold, skipped = the rest. Together they are the slots
# resident a stage built.
_C_FOLD_VISITED = _metrics.counter("devwindow.fold.slots.visited")
_C_FOLD_SKIPPED = _metrics.counter("devwindow.fold.slots.skipped")
# Stages built with and without a cut by the matched series (their sum
# is devwindow.stage.miss): how often the series dimension of the zone
# maps engages.
_C_FOLD_NARROWED = _metrics.counter("devwindow.fold.stages.narrowed")
_C_FOLD_WHOLE = _metrics.counter("devwindow.fold.stages.whole")
# window.chunk_fold calls: a stage built issues one for every group of
# up to kernels._FOLD_GROUP chunks of one shape class its selection
# picked a block of (kernels.fold_groups), so dispatches /
# devwindow.stage.miss is the fold calls a stage, which grow with the
# span of the range, a group at a time, where the slots visited need
# not. stage.programs is every device program a stage build issued
# from Python: the start (the accumulators), each fold call and the
# finish, so 2 + the calls (a shard of the sharded window: its own
# start and finish), and each one a place where the stage's thread
# lets the interpreter lock go and has to win it back.
_C_FOLD_DISPATCHES = _metrics.counter("devwindow.fold.dispatches")
_C_STAGE_PROGRAMS = _metrics.counter("devwindow.stage.programs")
# The sharded window's stages (storage/devshard.py): the shards a stage
# built was folded on (a window_series_stage_chunks call each, so
# shards / stage.miss is the fan-out: every shard of the metric where
# no shard is dropped), and the bytes of the shards' grids that went
# from their device to the combine device.
_C_STAGE_SHARDS = _metrics.counter("devwindow.stage.shards")
_C_GATHER_BYTES = _metrics.counter("mesh.resident.gather.bytes")
# The updates the folds' scatters were handed (kernels._scatter_runs:
# one a run of equal (series, bucket) and not one a slot), beside
# devwindow.fold.slots.visited: their ratio is what the run reduction
# left of the scatters' work (a gauge: query/grid.py, _Handed).
_FOLD_HANDED = _Handed()
_metrics.gauge("devwindow.fold.updates", _FOLD_HANDED.total)


def _device_id(device) -> int | None:
    """A span's tag for the device a shard is pinned to (None: the
    default placement)."""
    return None if device is None else int(device.id)


def _fold_extent(cols) -> tuple[int, ...]:
    """DevChunks.fold_extent() of a resident window's columns, summed
    over its shards: (blocks picked, blocks in all, slots picked, slots
    in all, chunks hit, fold calls), and after them the device programs
    the stage build issues: the calls, for each shard its start and its
    finish, and the join of several shards."""
    parts = list(filter(None, cols.shards))
    sums = tuple(map(sum, zip(*(p.fold_extent() for p in parts))))
    return sums + (sums[5] + 2 * len(parts) + (len(parts) > 1),)


class ResidentPlan:
    """``serve`` answers from the window, or returns None and the
    planner tries the next plan (CPU backend, un-downsampled queries,
    dirty/evicted windows, unknown UIDs, out-of-int32 epochs/ranges, a
    device out of memory). The plan owns its caches, its counters and
    its resident.* spans."""

    label = "resident"
    storage_free = True     # tried before the rollup tiers, and served
    #                         where load is shed (rollup_only)

    def __init__(self, tsdb, backend: str, mesh, tag_filters) -> None:
        self.tsdb = tsdb
        self.backend = backend
        self.mesh = mesh
        # The executor's: a tag-filter map -> UID-level (exact, group_bys).
        self._tag_filters = tag_filters
        # Device-resident include/gmap (and a sharded window's join
        # rows) by (window instance, metric, filter), the generation in
        # the value.
        self.mask_cache = LRUCache(128)
        # The groups of (window instance, metric, filter) until the
        # directory grows.
        self.plan_cache = LRUCache(128)
        # The stage cache: a miss builds a stage, the device's whole
        # cost of a resident sub-query.
        self.stage_cache = LRUCache(4)
        # Several shards' stages: (device, programs' statics, the
        # window's chunk shape classes) whose programs a shard's device
        # has compiled (_warm_shards). One forgotten is warmed again,
        # from jit's own cache.
        self.shard_warm = LRUCache(256)

    def serve(self, spec, start: int, end: int, agg):
        dw = getattr(self.tsdb, "devwindow", None)
        # A mesh executor serves the resident path only through a
        # window sharded over the mesh (devshard.py): a window of one
        # shard keeps declining (its columns live on one device while
        # the mesh plans expect sharding).
        if (dw is None or self.backend == "cpu"
                or (self.mesh is not None and dw.n_shards == 1)
                or not spec.downsample
                or agg.kind not in ("moment", "percentile")
                or Aggregators.get(spec.downsample[1]).kind
                != "moment"):
            return None
        interval, dsagg = spec.downsample
        qbase = start - start % interval
        # Rebased in-range timestamps span up to end - qbase; past int32
        # they would wrap in the kernels. Checked BEFORE touching the
        # window: dw.columns() forces a staged upload + drain, wasted on
        # a query that can never be served from it.
        if end - qbase > IMAX:
            return None
        try:
            metric_uid = self.tsdb.metrics.get_id(spec.metric)
            exact, group_bys = self._tag_filters(spec.tags)
        except NoSuchUniqueName:
            return None  # scan path raises the canonical error
        # The window serves queries from its raw chunk list (no
        # concatenated copy — the window can approach the whole HBM);
        # every moment family folds chunk-wise, dev included (Chan M2
        # combination, ops/kernels._chunk_fold).
        # From here the resident.* spans are the children of
        # planner.pick, in order and together tiling it (README,
        # "Observability", says what each one times).
        with obs_trace.span("resident.columns") as sp:
            cols = dw.chunk_columns(metric_uid, start, end)
            if sp is not None and cols is not None:
                chunks = [c for sc in cols.shards if sc is not None
                          for c in sc.chunks]
                sp.tags["chunks"] = len(chunks)
                sp.tags["points"] = sum(int(c[0].shape[0])
                                        for c in chunks)
        if cols is None:
            # On planner.pick, the span open around this call: why the
            # window declined a request of a kind it serves.
            sp = obs_trace.current_span()
            if sp is not None:
                sp.tags["miss"] = dw.last_miss()
            return None
        with obs_trace.span("resident.groups") as gsp:
            named, grid, plan_hit = self._groups(
                dw, metric_uid, cols, exact, group_bys)
            if not grid.gkeys:
                return []

            # The shift (qbase - epoch) participates in arithmetic on
            # device (rel_ts - shift in window_series_stage) — unlike
            # lo/hi, which are comparison-only and clamp safely. If it
            # doesn't fit in int32 (e.g. an all-time query against a
            # metric whose epoch is past 2^31), fall back to the scan
            # path rather than silently mis-bucketing (devstore's
            # exact-or-fall-back contract). A window carries one epoch
            # PER shard; all must fit.
            live = [(i, sc) for i, sc in enumerate(cols.shards)
                    if sc is not None]
            if not all(IMIN <= qbase - sc.epoch <= IMAX
                       for _i, sc in live):
                return None
            b_live = int((end - qbase) // interval + 1)
            num_buckets = _pad_size(b_live)
            S_all = len(cols.series_keys)
            S_pad = _pad_size(S_all)
            if S_pad * num_buckets > kernels.STAGE_GRID_MAX:
                # The largest grid the daemon kept room for beside the
                # window at boot (tools/cli.py), and far under where the
                # kernels' int32 per-(series, bucket) segment ids would
                # wrap. Scan path handles it (per-group kernels, smaller
                # grids).
                return None
            # Device-resident include/gmap, cached per (window instance,
            # plan, generation, padding): every fresh host array argument
            # is its own transfer, so repeat dashboard queries should not
            # re-upload masks that only change when the series directory
            # grows (generation bump invalidates;
            # instance_id guards against a replacement window whose counters
            # restart at 0 — devstore's cache-keying contract).
            fk = _filter_key(exact, group_bys)
            mkey = (dw.instance_id, metric_uid, fk)
            hit = self.mask_cache.get(mkey)
            mask_hit = hit is not None and hit[0] == cols.generation
            if mask_hit:
                include, gmap, sids = hit[1:]
            else:
                include, gmap = qgrid.group_masks(grid, S_pad)
                # Committed where the first shard that holds chunks is
                # pinned (None: the default placement): the device
                # several shards' grids are joined on, so the apply's
                # inputs lie with them.
                tgt = next((sc.window.device
                            for _i, sc in live if sc.chunks), None)
                # The matched series ids, sorted: what the stage's block
                # selection is narrowed by.
                sids = np.flatnonzero(include)
                include = jax.device_put(include, tgt)
                gmap = jax.device_put(gmap, tgt)
                # Generation lives in the VALUE (the plan_cache
                # pattern): a directory growth overwrites in place, so dead
                # generations never accumulate device arrays.
                self.mask_cache.put(
                    mkey, (cols.generation, include, gmap, sids))
            if gsp is not None:
                gsp.tags.update(series=S_all, groups=len(grid.gkeys),
                                plan_hit=plan_hit, mask_hit=mask_hit)
        rate_kw = qgrid.rate_kw(spec)
        # The heavy N-point half of a window query (range mask +
        # per-series downsample [+ rate]) caches per (window instance,
        # metric, data version, range, interval, downsample, rate, the
        # filter its blocks were narrowed by) and stays device-resident.
        # A request whose matched series cut no block out (it matched
        # every series, or every block in range holds one of them)
        # folds the blocks of its range whole: that stage is good for
        # any tag filter, any group-by, moments and p50/p95/p99 alike,
        # which then pay only the [S, B]-sized apply + one dispatch.
        # Any other folds only the blocks its series can lie in
        # (DevChunks.narrowed): its grids are whole for the rows its
        # own include mask keeps and partial for the others, so its
        # stage answers that filter alone.
        cache = self.stage_cache
        with obs_trace.span("resident.stage") as ssp:
            whole, cols = cols, cols.narrowed(sids, start, end)
            narrowed = cols is not whole
            skey = (dw.instance_id, metric_uid, cols.version, start, end,
                    interval, dsagg, tuple(sorted(rate_kw.items())),
                    fk if narrowed else None)
            stage = cache.get(skey)
            (_C_STAGE_MISS if stage is None else _C_STAGE_HIT).inc()
            if ssp is not None:
                ssp.tags["hit"] = stage is not None
                ssp.tags["narrowed"] = narrowed
                ssp.tags["series"] = len(sids)
            if stage is None:
                (_C_FOLD_NARROWED if narrowed else _C_FOLD_WHOLE).inc()
                picked, of, visited, resident, folded, calls, programs = \
                    _fold_extent(cols)
                _C_FOLD_VISITED.inc(visited)
                _C_FOLD_SKIPPED.inc(resident - visited)
                _C_FOLD_DISPATCHES.inc(calls)
                _C_STAGE_PROGRAMS.inc(programs)
                if ssp is not None:
                    ssp.tags["chunks"] = folded
                    ssp.tags["calls"] = calls
                    ssp.tags["blocks"] = picked
                    ssp.tags["blocks_total"] = of
                try:
                    grids = self._stage(
                        (dw.instance_id, metric_uid), cols, start, end,
                        qbase, num_buckets=num_buckets, S_pad=S_pad,
                        interval=interval, dsagg=dsagg, rate_kw=rate_kw)
                except Exception as e:
                    # A near-HBM window can still OOM building the stage
                    # grids; degrade to the storage scan (the
                    # exact-or-fall-back contract) instead of erroring.
                    if _is_device_oom(e):
                        return None
                    raise
                # [5] fills with the host copy of presence on first fetch.
                stage = list(grids) + [None]
                # Stages of this metric's EARLIER data versions can never
                # hit again (version is monotonic) but each pins [S, B]
                # grids in HBM the devwindow's own budget can't see — drop
                # them before the LRU cap so active ingest (a version bump
                # per flush) doesn't strand dead grids on device.
                for k in cache.keys():
                    if k[:2] == (dw.instance_id, metric_uid) \
                            and k[2] != cols.version:
                        cache.pop(k)
                        _C_STAGE_EVICTED.inc()
                cache.put(skey, stage)
        ngroups, shrink = qgrid.clip(grid, num_buckets, b_live,
                                     self.tsdb.config.wire_bf16)
        # The applies allocate fresh [S,B]/[G,B] buffers on a device the
        # resident window may have filled to within a few hundred MB of
        # HBM — an OOM here (or in the fetch's staging buffer) must
        # degrade to the scan path exactly like a stage-build OOM, or
        # the exact-or-fall-back contract breaks precisely in the
        # 1B-resident regime it exists for.
        try:
            with obs_trace.span("resident.apply", g_out=shrink["g_out"],
                                b_out=shrink["b_out"]):
                gv, gm = qgrid.apply(stage, include, gmap, agg,
                                     spec.aggregator, ngroups, shrink)
            gv, gm = qgrid.fetch("resident", gv, gm, stage)
        except Exception as e:
            if _is_device_oom(e):
                # Drop the stage too: leaving it cached would pin its
                # [S, B] grids in the very HBM that just ran out, and
                # every later query of this panel would re-dispatch a
                # doomed apply before falling back.
                if cache.pop(skey, None) is not None:
                    _C_STAGE_EVICTED.inc()
                return None
            raise
        return qgrid.results("resident", spec.metric, grid,
                             named.__getitem__, stage, gv, gm,
                             shrink["b_out"], interval, qbase)

    def _stage(self, of: tuple, cols, start: int, end: int, qbase: int,
               *, num_buckets: int, S_pad: int, interval: int,
               dsagg: str, rate_kw: dict):
        """The stage half of a resident query, over the window's
        shards (one, the plain window's own; or the mesh-SHARDED hot
        set's, storage/devshard.py): each shard's chunk fold runs on
        its OWN device (async dispatch overlaps the shards), then only
        the [S_shard, B] stage grids — never the N-point columns —
        travel to the first shard's device, where one program
        (kernels.shard_combine) lays their rows out in
        combined-directory order, padded to S_pad. Row order equals
        ``cols.series_keys`` order, so include/gmap and the apply
        kernels are oblivious to sharding. ``of``: (window instance,
        metric), which with ``cols.generation`` names the directory.
        One live shard whose grids are S_pad high already is its own
        join: nothing moves, no program runs, no span opens and
        nothing is counted for it.

        Nothing here compiles for a metric, a host or a range of its
        own: every shard folds into grids of one padded height (that of
        the fullest), on its own device whether or not a block of its
        chunks was picked, and which rows the join takes from where is
        an array. What a request of some kind compiles, the first
        request of that kind has compiled, on every device: a program
        belongs to one device, the shard a one-host panel folds on
        follows the host it drew, and a metric's series fall to the
        shards in their own numbers.

        Numeric contract (declared, README "Serving mesh"): the
        per-shard folds are the SAME f32 kernels whatever the shard
        count and a series never splits across shards, so count/min/max
        rows are byte-identical across shard counts while sum/avg/dev
        rows agree to f32 tolerance (bucket partial sums reassociate
        across chunk boundaries that fall differently per shard).

        Returns the window_series_stage grids (the caller has seen to
        it that a shard is live and every shard's epoch shift fits
        int32).
        """
        live = [(i, sc) for i, sc in enumerate(cols.shards)
                if sc is not None]
        held = [len(sc.series_keys) for _i, sc in live]
        height = _pad_size(max(held))
        statics = dict(num_series=height, num_buckets=num_buckets,
                       interval=interval, agg_down=dsagg, **rate_kw)

        def fold(sc):
            grids = kernels.window_series_stage_chunks(
                sc.chunks,
                clamp32(start - sc.epoch), clamp32(end - sc.epoch),
                np.int32(qbase - sc.epoch),
                blocks=sc.blocks, block=sc.block,
                device=sc.window.device, **statics)
            _FOLD_HANDED.add(grids[5])
            return grids[:5]

        if len(live) == 1 and height == S_pad:
            return fold(live[0][1])
        self._warm_shards([sc.window for _i, sc in live],
                          live[0][1].block, statics)
        parts = []
        for i, sc in live:
            # The host's time in this shard's stage: its start, its fold
            # dispatches, its finish (the device runs on behind it).
            with obs_trace.span("resident.shard", shard=i) as sp:
                parts.append(fold(sc))
                if sp is not None:
                    sp.tags.update(
                        device=_device_id(sc.window.device),
                        series=len(sc.series_keys),
                        chunks=sum(len(b) > 0 for b in sc.blocks))
        _C_STAGE_SHARDS.inc(len(parts))
        # What brings the shards' grids to the combine device (the
        # first shard's) and joins them: the copies between devices and
        # the one program that lays the rows out.
        with obs_trace.span("resident.gather", shards=len(parts)) as sp:
            target = next(iter(parts[0][0].devices()))
            moved = sum(g.nbytes for grids in parts for g in grids
                        if target not in g.devices())
            hit = self.mask_cache.get(of + ("shard_rows",))
            if hit is not None and hit[:2] == (cols.generation, height):
                rows = hit[2]
            else:
                # Row r of the joined grids: the shard its series lives
                # in, times the height, plus the series' row there; a
                # padding row, one past every shard's.
                rows = np.full(S_pad, len(live) * height, np.int32)
                rows[:sum(held)] = np.concatenate(
                    [n * height + np.arange(mine)
                     for n, mine in enumerate(held)])
                rows = jax.device_put(rows, target)
                self.mask_cache.put(
                    of + ("shard_rows",), (cols.generation, height, rows))
            outs = kernels.shard_combine(
                tuple(tuple(jax.device_put(g, target) for g in grids)
                      for grids in parts), rows)
            _C_GATHER_BYTES.inc(moved)
            if sp is not None:
                sp.tags["bytes"] = moved
        return outs

    def _warm_shards(self, windows, block: int, statics: dict) -> None:
        """Compile, on the device of each of the shards' ``windows`` not
        warmed for it yet, every program a stage of ``statics`` can run
        there, before the first stage of the kind is built, whichever
        shard that request's own selection folds on: a stage over one
        chunk of each shape class the window holds, a block of each
        visited over a range nothing lies in, built and thrown away. A
        program belongs to one device, the shard a one-host panel folds
        on follows the host it drew and a metric's chunks pad to their
        own classes, so without it the first request to fold on a
        shard, or on a class, compiles under that request. The shards
        do it side by side: the compiler works outside the interpreter
        lock."""
        programs = tuple(sorted(statics.items()))
        cold = {}
        for window in windows:
            key = (_device_id(window.device), programs, window.chunk_sizes)
            if self.shard_warm.get(key) is None:
                cold[key] = window
        if not cold:
            return

        def warm(window):
            classes = window.chunk_classes()
            kernels.window_series_stage_chunks(
                classes, np.int32(1), np.int32(0), np.int32(0),
                blocks=[(0,)] * len(classes), block=block,
                device=window.device, **statics)
        with concurrent.futures.ThreadPoolExecutor(len(cold)) as pool:
            list(pool.map(warm, cold.values()))
        for key in cold:
            self.shard_warm.put(key, True)

    def _groups(self, dw, metric_uid: bytes, cols, exact, group_bys):
        """Filter + group the window's series directory on host UIDs
        (``qgrid.series_selector``; sid = position in the directory).

        Returns ({sid: named_tags}, the groups as the answer takes them
        (_GridGroups, their labels kept from the first answer on),
        whether the plan cache held them); cached per (window instance,
        metric, filter) until the directory grows.
        ``dw`` is the SAME window object ``cols`` came from (passed by
        the caller, not re-read from self.tsdb — a swap between capture
        and here must not cache the old window's plan under the new
        window's instance_id)."""
        fkey = (dw.instance_id, metric_uid,
                _filter_key(exact, group_bys))
        hit = self.plan_cache.get(fkey)
        if hit is not None and hit[0] == cols.generation:
            return hit[1], hit[2], True
        selector = qgrid.series_selector(exact, group_bys)
        groups: dict[tuple, list[int]] = {}
        named: dict[int, dict[str, str]] = {}
        for sid, skey in enumerate(cols.series_keys):
            g = selector(skey)
            if g is None:
                continue
            groups.setdefault(g, []).append(sid)
            named[sid] = qgrid.named_tags(self.tsdb, skey)
        grid = _GridGroups(groups)
        self.plan_cache.put(fkey, (cols.generation, named, grid))
        return named, grid, False
