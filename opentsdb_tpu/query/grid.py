"""What a grid plan's answer is made with.

The resident and the fused plan (query/resident.py, query/fused.py)
both end in a stage's five [series, bucket] grids on the device; what
follows the stage is written here once (the tail, below). Also here,
because more than one plan needs them: the padding ladders, the filter
key, the series selector, and the tally of the updates that the stages
of the plans past the horizon (raw, fused) were handed.
"""

from __future__ import annotations

import collections
import itertools
import threading
from typing import NamedTuple

import jax
import numpy as np

from opentsdb_tpu.core import codec
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS as _metrics
from opentsdb_tpu.ops import kernels

# A stage's count of the updates its scatters were handed
# (kernels._scatter_runs: one a run of equal (series, bucket) and not
# one a slot) is a device scalar; it waits in a _Handed until the stats
# are read (or _HANDED_MAX have gathered), so no sub-query pays a
# transfer for it. A gauge over a running total, and not a counter, for
# that reason.
_HANDED_MAX = 512


class _Handed:
    """The running total of the counts that stages left on the device."""

    def __init__(self):
        self._waiting: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._total = 0

    def total(self) -> int:
        """The updates handed over since boot: the stages' counts that
        were still on the device fetched and added to the total."""
        with self._lock:
            # One drainer at a time, and the others only append.
            handed = [self._waiting.popleft()
                      for _ in range(len(self._waiting))]
            if handed:
                self._total += sum(map(int, jax.device_get(handed)))
            return self._total

    def add(self, handed) -> None:
        """Keep one stage's count for the next reading of the stats."""
        self._waiting.append(handed)
        if len(self._waiting) > _HANDED_MAX:
            self.total()


# The same of the plans that read past the horizon: the slots of the
# streams their stages were given (the raw plan's packed stream, the
# fused plan's whole blocks or matched points, padding and all) and the
# updates kernels._series_stage's scatters were handed for them.
_C_STAGE_SLOTS = _metrics.counter("query.stage.slots")
_STAGE_HANDED = _Handed()
_metrics.gauge("query.stage.updates", _STAGE_HANDED.total)


def _stage_handed(handed, slots: int) -> None:
    """Count one kernels._series_stage: ``handed`` its device scalar,
    ``slots`` the length of the stream it was given."""
    _C_STAGE_SLOTS.inc(slots)
    _STAGE_HANDED.add(handed)


# The groups a grid plan (resident, fused) answered, by where their
# labels came from: kept = taken from the plan that made the groups
# (_GridGroups.labels), computed = worked out on the request (the
# first answer of a plan builds its labels, and a group with a member
# that has no point in range is always labelled anew, over its live
# members). kept / (kept + computed) says how often the plans are
# still held when their groups are asked for again.
_C_LABELS_KEPT = _metrics.counter("query.results.labels.kept")
_C_LABELS_COMPUTED = _metrics.counter("query.results.labels.computed")


class QueryResult(NamedTuple):
    metric: str
    tags: dict[str, str]
    aggregated_tags: list[str]
    timestamps: np.ndarray          # int64 epoch seconds
    values: np.ndarray              # float64


# The kernels rebase timestamps to int32 seconds: a bound of a range
# is comparison-only and clamps safely (``clamp32``); a shift takes part
# in arithmetic on the device and has to fit, or the plan declines.
IMIN, IMAX = -(2**31), 2**31 - 1


def clamp32(x: int):
    return np.int32(min(max(x, IMIN), IMAX))


def _pad_size(n: int) -> int:
    """Round up to a power of two (min 16) to bound jit recompilations."""
    size = 16
    while size < n:
        size *= 2
    return size


def _pad64(n: int) -> int:
    """Round up to a multiple of 64 (min 64): fetch-slice quantization —
    fine enough to cut padded-transfer waste, coarse enough to bound
    the distinct static shapes the apply kernels compile for."""
    return max((n + 63) // 64 * 64, 64)


def _is_device_oom(e: Exception) -> bool:
    """Device allocation failure (XLA RESOURCE_EXHAUSTED) — the one
    non-contract error the devwindow path converts into a scan-path
    fallback rather than raising."""
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


def _filter_key(exact, group_bys):
    """Canonical hashable form of a UID-level (exact, group_bys) tag
    filter — the shared component of every devwindow cache key (plan,
    mask, quantile stage). One definition so the keys can't
    desynchronize."""
    return (tuple(sorted(exact)),
            tuple(sorted((k, tuple(v) if v else None)
                         for k, v in group_bys)))


def rate_kw(spec) -> dict:
    """Static+traced rate args threaded into the fused kernels."""
    return dict(
        rate=spec.rate,
        counter_max=spec.counter_max if spec.counter else 0.0,
        reset_value=spec.reset_value or 0.0,
        counter=spec.counter,
        drop_resets=spec.reset_value is not None)


def series_selector(exact, group_bys):
    """The ONE tag-filter/group-by predicate behind the resident-
    window and fused plans (they must answer identically, so the
    semantics live in one function): series_key -> group key tuple
    when the series matches, None when filtered out. The fused
    path pushes this down into compress/fused.gather, where it
    runs against block keys BEFORE payload decode."""
    group_by_keys = sorted(k for k, _ in group_bys)
    want = dict(exact)
    gb = {k: (set(v) if v else None) for k, v in group_bys}

    def selector(skey: bytes):
        tag_uids = codec.series_tag_uids(skey)
        for k, v in want.items():
            if tag_uids.get(k) != v:
                return None
        for k, allowed in gb.items():
            v = tag_uids.get(k)
            if v is None or (allowed is not None
                             and v not in allowed):
                return None
        return tuple(tag_uids.get(k, b"") for k in group_by_keys)

    return selector


def named_tags(tsdb, skey: bytes) -> dict[str, str]:
    return {tsdb.tagk.get_name(k): tsdb.tagv.get_name(v)
            for k, v in codec.series_tag_uids(skey).items()}


def group_tags(members: list[dict[str, str]]):
    """Intersection tags + aggregated (differing) tag names of a
    group, from its series' named tags.

    Parity: reference SpanGroup.computeTags (:149-173)."""
    common = dict(members[0])
    keys = set(members[0])
    for tags in members[1:]:
        keys &= set(tags)
        for k in list(common):
            if tags.get(k) != common[k]:
                del common[k]
    common = {k: v for k, v in common.items() if k in keys}
    aggregated = sorted(
        {k for tags in members for k in tags} - set(common))
    return common, aggregated


class KeptTags(dict):
    """The tags of a label kept with its plan (``_GridGroups.labels``),
    with the label's ``aggregated`` list and room for the ``text`` an
    encoder of answers made of the two (server/qjson.py fills it on the
    label's first answer, from the event-loop thread alone), so that
    what was formatted lives as long as the label and goes with its
    plan."""

    __slots__ = ("aggregated", "text")

    def __init__(self, tags: dict[str, str],
                 aggregated: list[str]) -> None:
        super().__init__(tags)
        self.aggregated = aggregated
        self.text: str | None = None


class _GridGroups:
    """The groups of a grid plan (resident, fused) as its answer takes
    them, kept with the plan that made the groups: the sorted group
    keys (row ``i`` of the fetched grids is ``gkeys[i]``), the groups'
    members as one flat array of series ids with the groups' offsets
    into it and their sizes, and, from the first answer on, one ``(tags, aggregated)``
    a group over its whole membership (``_grid_results`` builds them).
    ``series_keys`` is the directory the ids are positions in, where
    the plan is told by it (fused); the resident plan is told by its
    window's generation."""

    __slots__ = ("gkeys", "members", "offsets", "sizes", "labels",
                 "series_keys")

    def __init__(self, groups: dict[tuple, list[int]],
                 series_keys: list[bytes] | None = None) -> None:
        self.gkeys = sorted(groups)
        self.sizes = np.array([len(groups[g]) for g in self.gkeys],
                              np.intp)
        self.offsets = np.zeros(len(self.gkeys) + 1, np.intp)
        np.cumsum(self.sizes, out=self.offsets[1:])
        self.members = np.fromiter(
            (sid for g in self.gkeys for sid in groups[g]), np.intp,
            int(self.offsets[-1]))
        self.labels: list[tuple[KeptTags, list[str]]] | None = None
        self.series_keys = series_keys


def _grid_results(metric: str, grid: _GridGroups, tags_of, has_points,
                  gv, gm, b_out: int, interval: int,
                  qbase: int) -> list[QueryResult]:
    """The answer of a grid plan out of its fetched grids, by whole-
    array operations: a result a group with a live member, in the
    order of ``grid.gkeys``.

    ``gv`` / ``gm`` are the fetched ``[g_out, b_out]`` values and their
    bit-packed mask (row ``i``: group ``i``), ``has_points`` the
    presence of every series id (a bool array), ``tags_of(sid)`` a series' named tags.
    A series with no point in range must not shape its group's labels
    nor leave an empty group behind (the scan path never sees it): a
    group with none alive is dropped, a group with all alive takes the
    labels kept with the plan, and a group in between is labelled anew
    over its live members.

    What is handed out is shared and read-only: the tag dicts and the
    aggregated lists between every answer of the plan (nothing under
    opentsdb_tpu/ writes to a QueryResult's fields), the timestamps
    between the results of one answer where their rows' masks are one
    (hosts that report in step), the values as rows or slices of one
    float64 array."""
    members, offsets = grid.members, grid.offsets
    alive = has_points[members]
    nlive = np.add.reduceat(alive, offsets[:-1], dtype=np.intp)
    rows = np.flatnonzero(nlive)
    if not len(rows):
        return []
    whole = (nlive == grid.sizes)[rows]

    def label(gi: int, live_only: bool):
        sids = members[offsets[gi]:offsets[gi + 1]]
        if live_only:
            sids = sids[alive[offsets[gi]:offsets[gi + 1]]]
        return group_tags(
            [tags_of(sid) for sid in sids.tolist()])

    kept = grid.labels is not None
    if not kept:
        # The plan's first answer (two at once build the same twice).
        grid.labels = [(KeptTags(tags, aggregated), aggregated)
                       for tags, aggregated in (
                           label(gi, False)
                           for gi in range(len(grid.gkeys)))]
    live = rows.tolist()
    labels = [lab if w else label(gi, True) for gi, w, lab
              in zip(live, whole.tolist(),
                     map(grid.labels.__getitem__, live))]
    n_kept = np.count_nonzero(whole) if kept else 0
    _C_LABELS_KEPT.inc(n_kept)
    _C_LABELS_COMPUTED.inc(len(rows) - n_kept)
    # The live rows: their values, and their masks as [R, b_out] bytes.
    gv = gv[rows]
    bits = np.unpackbits(gm[rows], axis=1, count=b_out)
    if (bits == bits[0]).all():
        cols = np.flatnonzero(bits[0])
        ts = cols.astype(np.int64) * interval + qbase
        ts.flags.writeable = False
        values = gv[:, cols].astype(np.float64)
        values.flags.writeable = False
        stamps = itertools.repeat(ts)
    else:
        r, c = np.nonzero(bits)
        flat_ts = c.astype(np.int64) * interval + qbase
        flat_ts.flags.writeable = False
        flat_vals = gv[r, c].astype(np.float64)
        flat_vals.flags.writeable = False
        ends = np.cumsum(bits.sum(axis=1, dtype=np.intp)).tolist()
        cuts = list(zip([0] + ends[:-1], ends))
        stamps = (flat_ts[lo:hi] for lo, hi in cuts)
        values = (flat_vals[lo:hi] for lo, hi in cuts)
    return [QueryResult(metric, tags, aggregated, ts, v)
            for (tags, aggregated), ts, v in zip(labels, stamps, values)]


# -- the tail both grid plans take once they hold a stage's grids ------
#
# A stage is a list: the five device grids of the window_series_stage
# contract (series_values, series_mask, filled, in_range, presence) and
# a sixth slot, None until the first fetch fills it with the host copy
# of presence (fetched once a stage, not once an answer).


def group_masks(grid: _GridGroups, S_pad: int):
    """The apply kernels' ``include`` mask and ``gmap`` of a plan's
    groups over a directory padded to ``S_pad``: a series nobody asked
    about maps to the last padded group and is masked out."""
    include = np.zeros(S_pad, bool)
    gmap = np.full(S_pad, _pad_size(len(grid.gkeys)) - 1, np.int32)
    include[grid.members] = True
    gmap[grid.members] = np.repeat(
        np.arange(len(grid.gkeys), dtype=np.int32), grid.sizes)
    return include, gmap


def clip(grid: _GridGroups, num_buckets: int, b_live: int,
         wire_bf16: bool) -> tuple[int, dict]:
    """(The apply's ``num_groups``, its ``g_out`` / ``b_out`` /
    ``wire_bf16``)."""
    # Shrink-wrap the fetch: clip to the live group/bucket counts
    # (64-quantized so statics don't churn recompiles) and bit-pack
    # the mask on device, so wide group-by queries do not fetch
    # padded [G, B] grids (what the fetch costs is the ledger's
    # fetch_ms).
    n = len(grid.gkeys)
    ngroups = 1 if n == 1 else _pad_size(n)
    return ngroups, dict(g_out=min(ngroups, _pad64(n)),
                         b_out=min(num_buckets, _pad64(b_live)),
                         wire_bf16=bool(wire_bf16))


def apply(stage, include, gmap, agg, aggregator: str, ngroups: int,
          shrink: dict):
    """Dispatch the [S, B] -> [G, B] half: the group values and their
    bit-packed mask, still on the device."""
    sv, sm, filled, in_range = stage[:4]
    if agg.kind == "percentile":
        return kernels.window_quantile_apply(
            sm, filled, in_range, include, gmap,
            np.array([agg.quantile], np.float32),
            num_groups=ngroups, **shrink)
    return kernels.window_moment_apply(
        sv, sm, filled, in_range, include, gmap,
        num_groups=ngroups, agg_group=aggregator, **shrink)


def fetch(prefix: str, gv, gm, stage=None):
    """The device's answer brought to the host under ``prefix``.wait
    (a traced request only) and ``prefix``.fetch; with a grid plan's
    ``stage``, its presence too where the stage has not fetched it."""
    if obs_trace.current_span() is not None:
        # Traced only: stage and apply are dispatches (JAX returns
        # before the device finishes), so without this sync the
        # device's time would all land in the fetch. Untraced the path
        # makes no such call.
        with obs_trace.span(prefix + ".wait"):
            jax.block_until_ready((gv, gm))
    # Series with no in-range points must not shape group labels or
    # emit empty groups — match the scan path, which never sees them.
    # One batched device_get — separate fetches would each pay a
    # transport round trip; presence is fetched once per stage.
    with obs_trace.span(prefix + ".fetch") as sp:
        if stage is not None and stage[5] is None:
            gv, gm, stage[5] = jax.device_get((gv, gm, stage[4]))
        else:
            gv, gm = jax.device_get((gv, gm))
        if sp is not None:
            sp.tags["bytes"] = int(gv.nbytes + gm.nbytes)
    return gv, gm


def results(prefix: str, metric: str, grid: _GridGroups, tags_of,
            stage, gv, gm, b_out: int, interval: int,
            qbase: int) -> list[QueryResult]:
    """``_grid_results`` of a fetched stage under ``prefix``.results."""
    with obs_trace.span(prefix + ".results") as sp:
        out = _grid_results(metric, grid, tags_of, stage[5], gv, gm,
                            b_out, interval, qbase)
        if sp is not None:
            sp.tags["results"] = len(out)
    return out
