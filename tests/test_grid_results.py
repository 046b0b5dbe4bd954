"""The one builder of a grid plan's answer (``_grid_results``, behind
the spans ``resident.results`` and ``fused.results``) against the
plain loop it replaced, kept here as the reference: a Python turn a
group over the fetched grids. Timestamps, values (their dtype too),
tags, aggregated tags and order are equal case for case, on the first
answer of a plan (its labels built) and on the second (its labels
kept); the counters say which; what is handed out is read-only."""

import ml_dtypes
import numpy as np
import pytest

from opentsdb_tpu.obs.registry import METRICS
from opentsdb_tpu.query.grid import (QueryResult, _GridGroups,
                                     _grid_results, group_tags)

QBASE = 1356998400
METRIC = "cpu.usage_user"
KEPT = METRICS.counter("query.results.labels.kept")
COMPUTED = METRICS.counter("query.results.labels.computed")


def reference(metric, groups, named, has_points, gv, gm, b_out, interval,
              qbase):
    """The loop of the resident plan as PR 42 left it."""
    gkeys = sorted(groups)
    gm = np.unpackbits(gm, axis=1, count=b_out).astype(bool)
    results = []
    for gi, gkey in enumerate(gkeys):
        live = [sid for sid in groups[gkey] if has_points[sid]]
        if not live:
            continue
        tags, aggregated = group_tags(
            [named[sid] for sid in live])
        mask = gm[gi]
        grid_ts = (np.flatnonzero(mask).astype(np.int64) * interval
                   + qbase)
        results.append(QueryResult(
            metric, tags, aggregated, grid_ts,
            gv[gi][mask].astype(np.float64)))
    return results


def tsbs_tags(host: int, dc: int = 0) -> dict[str, str]:
    return {"hostname": f"host_{host}", "region": f"r{host % 3}",
            "datacenter": f"dc{dc}", "rack": str(host % 7),
            "os": "Ubuntu16.10", "arch": "x64", "team": "SF",
            "service": str(host % 5)}


def make(ngroups, per_group=1, b_out=64, one_mask=True, dead=(),
         dead_groups=(), differing=False, dtype=np.float32, seed=3):
    """Groups keyed as the selector keys them (a tuple of tag-value
    uids), ``per_group`` series each, sids dealt round-robin so that a
    group's members are not contiguous; ``dead`` sids and every member
    of ``dead_groups`` have no point in range."""
    rng = np.random.default_rng(seed)
    nseries = ngroups * per_group
    groups, named = {}, {}
    for sid in range(nseries):
        g = sid % ngroups
        # Keys that sort in another order than the groups were made in.
        gkey = (((g * 7919) % 10007).to_bytes(3, "big"),)
        groups.setdefault(gkey, []).append(sid)
        named[sid] = tsbs_tags(g, dc=sid // ngroups if differing else 0)
        if differing and sid // ngroups == 1:
            named[sid]["only_here"] = "x"
    has_points = np.ones(max(16, nseries + 5), bool)
    has_points[nseries:] = False
    has_points[list(dead)] = False
    for g in dead_groups:
        has_points[groups[sorted(groups)[g]]] = False
    g_out = 1 if ngroups == 1 else max((ngroups + 63) // 64 * 64, 64)
    gv = rng.normal(50, 20, (g_out, b_out)).astype(np.float32).astype(dtype)
    if one_mask:
        mask = np.tile(rng.random(b_out) < 0.4, (g_out, 1))
    else:
        mask = rng.random((g_out, b_out)) < 0.4
        if ngroups > 2:
            mask[1] = False         # a live group with no bucket at all
    return groups, named, has_points, gv, np.packbits(mask, axis=1), b_out


CASES = {
    "one-series groups": dict(ngroups=300),
    "many-series groups whose tags differ": dict(
        ngroups=40, per_group=4, differing=True),
    "a group with one member without points": dict(
        ngroups=40, per_group=4, differing=True, dead=(45,)),
    "a group with no live member": dict(
        ngroups=70, per_group=2, dead_groups=(0, 33, 69)),
    "rows with different masks": dict(ngroups=130, one_mask=False),
    "rows with one mask": dict(ngroups=130, one_mask=True),
    "a single group": dict(ngroups=1, per_group=9, differing=True),
    "a single group, one member dead": dict(
        ngroups=1, per_group=9, differing=True, dead=(4,)),
    "a grid narrower than 64 buckets": dict(ngroups=20, b_out=16),
    "a grid wider than 64 buckets": dict(
        ngroups=20, b_out=192, one_mask=False),
    "bf16-wire values": dict(ngroups=90, dtype=ml_dtypes.bfloat16),
    "bf16-wire values, different masks": dict(
        ngroups=90, dtype=ml_dtypes.bfloat16, one_mask=False),
    "no group at all": dict(ngroups=0),
    "every group dead": dict(ngroups=5, dead_groups=(0, 1, 2, 3, 4)),
    "dead, partly live and whole groups, different masks": dict(
        ngroups=64, per_group=3, differing=True, one_mask=False,
        dead=(1, 70, 130), dead_groups=(5, 63)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_builder_equals_the_loop_it_replaced(case):
    groups, named, has_points, gv, gm, b_out = make(**CASES[case])
    interval = 60
    want = reference(METRIC, groups, named, has_points, gv, gm, b_out,
                     interval, QBASE)
    grid = _GridGroups(groups)
    assert grid.gkeys == sorted(groups) and grid.labels is None
    partly = sum(
        0 < sum(bool(has_points[s]) for s in sids) < len(sids)
        for sids in groups.values())
    for turn in range(2):
        kept, computed = KEPT.value, COMPUTED.value
        got = _grid_results(METRIC, grid, named.__getitem__, has_points,
                            gv, gm, b_out, interval, QBASE)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.metric == w.metric
            assert g.tags == w.tags
            assert g.aggregated_tags == w.aggregated_tags
            assert g.timestamps.dtype == np.int64
            assert g.values.dtype == np.float64
            np.testing.assert_array_equal(g.timestamps, w.timestamps)
            np.testing.assert_array_equal(g.values, w.values)
            assert not g.timestamps.flags.writeable
            assert not g.values.flags.writeable
        # The first answer of a plan builds its labels; the second
        # takes them, but for the groups with a member without points.
        moved = (KEPT.value - kept, COMPUTED.value - computed)
        if turn == 0 or not want:
            assert moved == (0, len(want))
        else:
            assert moved == (len(want) - partly, partly)
    if want and CASES[case].get("one_mask", True):
        # Rows of one mask share one timestamps array.
        assert all(g.timestamps is got[0].timestamps for g in got)
    if CASES[case].get("differing") and want:
        assert any(g.aggregated_tags for g in got)
    # The inputs are left as they were fetched.
    assert gv.flags.writeable and gm.flags.writeable


def test_kept_labels_never_hold_a_series_without_points():
    """A group's kept labels are over its whole membership: while one
    member has no point in range the group is labelled over the live
    ones, and takes the kept labels again when all are back."""
    groups, named, has_points, gv, gm, b_out = make(
        ngroups=3, per_group=2, differing=True)
    grid = _GridGroups(groups)
    whole = _grid_results(METRIC, grid, named.__getitem__, has_points, gv,
                          gm, b_out, 60, QBASE)
    assert all("datacenter" in r.aggregated_tags for r in whole)
    has_points[3] = False           # the second member of one group
    part = _grid_results(METRIC, grid, named.__getitem__, has_points, gv,
                         gm, b_out, 60, QBASE)
    want = reference(METRIC, groups, named, has_points, gv, gm, b_out, 60,
                     QBASE)
    assert [(r.tags, r.aggregated_tags) for r in part] == \
        [(r.tags, r.aggregated_tags) for r in want]
    lone = [r for r in part if not r.aggregated_tags]
    assert len(lone) == 1 and lone[0].tags["datacenter"] == "dc0"
    has_points[3] = True
    back = _grid_results(METRIC, grid, named.__getitem__, has_points, gv,
                         gm, b_out, 60, QBASE)
    for a, b in zip(whole, back):
        assert a.tags is b.tags and a.aggregated_tags is b.aggregated_tags
