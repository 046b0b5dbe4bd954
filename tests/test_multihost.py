"""Hybrid ICI x DCN mesh tests on the virtual 8-device CPU platform.

The two-level (chip -> host -> global) merges must return the same
answers as the flat 1-D sharded path and the unsharded kernels: the mesh
topology is an execution detail, never a semantics change.
"""

import jax
import numpy as np
import pytest

from opentsdb_tpu.ops import kernels, sketches
from opentsdb_tpu.parallel.mesh import HOST_AXIS, SERIES_AXIS
from opentsdb_tpu.parallel.multihost import (
    hybrid_downsample_group,
    hybrid_hll_distinct,
    hybrid_tdigest,
    init_multihost,
    make_hybrid_mesh,
)
from opentsdb_tpu.parallel.sharded import pack_shards

RNG = np.random.default_rng(7)


def random_series(n_points, span=7200):
    ts = np.sort(RNG.choice(np.arange(span), size=n_points,
                            replace=False)).astype(np.int64)
    return ts, RNG.normal(50.0, 10.0, size=n_points)


@pytest.fixture(scope="module", params=[(2, 4), (4, 2)])
def mesh(request):
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    h, c = request.param
    return make_hybrid_mesh(h, c)


class TestMakeHybridMesh:
    def test_axes_and_shape(self):
        m = make_hybrid_mesh(2, 4)
        assert m.axis_names == (HOST_AXIS, SERIES_AXIS)
        assert m.devices.shape == (2, 4)

    def test_bad_fold_rejected(self):
        with pytest.raises(ValueError):
            make_hybrid_mesh(3, 3)

    def test_single_process_init_is_noop(self):
        assert init_multihost() is False


class TestHybridDownsampleGroup:
    @pytest.mark.parametrize("agg_group", ["sum", "avg", "dev", "min",
                                           "max", "count", "zimsum",
                                           "mimmin"])
    def test_matches_unsharded(self, mesh, agg_group):
        series = [random_series(RNG.integers(10, 80)) for _ in range(24)]
        interval = 300
        B = 7200 // interval
        ts, vals, sid, valid, sps = pack_shards(series, 8)
        gv, gm = hybrid_downsample_group(
            ts, vals, sid, valid, mesh=mesh, series_per_shard=sps,
            num_buckets=B, interval=interval, agg_down="avg",
            agg_group=agg_group)
        gv, gm = np.asarray(gv), np.asarray(gm)

        # Unsharded oracle: same fused kernel with globally renumbered sids.
        flat_ts = np.concatenate([s[0] for s in series]).astype(np.int32)
        flat_vals = np.concatenate([s[1] for s in series]).astype(np.float32)
        flat_sid = np.concatenate(
            [np.full(len(s[0]), i, np.int32) for i, s in
             enumerate(series)])
        ref = kernels.downsample_group(
            flat_ts, flat_vals, flat_sid, np.ones(len(flat_ts), bool),
            num_series=len(series), num_buckets=B, interval=interval,
            agg_down="avg", agg_group=agg_group)
        np.testing.assert_array_equal(gm, np.asarray(ref["group_mask"]))
        np.testing.assert_allclose(
            gv[gm], np.asarray(ref["group_values"])[gm],
            rtol=2e-5, atol=1e-4)


class TestHybridSketches:
    def test_hll_matches_exact_within_error(self, mesh):
        distinct = 5000
        items = RNG.integers(0, distinct, (8, 4000)).astype(np.int32)
        valid = np.ones_like(items, bool)
        est = float(hybrid_hll_distinct(items, valid, mesh=mesh, p=14))
        exact = len(np.unique(items))
        assert abs(est - exact) / exact < 0.05

    def test_tdigest_matches_exact_within_error(self, mesh):
        values = RNG.normal(100.0, 25.0, (8, 5000)).astype(np.float32)
        valid = np.ones_like(values, bool)
        qs = np.asarray([0.1, 0.5, 0.95, 0.99], np.float32)
        got = np.asarray(hybrid_tdigest(values, valid, qs, mesh=mesh))
        exact = np.quantile(values.reshape(-1), qs)
        np.testing.assert_allclose(got, exact, rtol=0.05)


def test_two_process_dcn_merge_end_to_end():
    """The committed multi-process proof (VERDICT r03 item 9): fork two
    OS processes joined via jax.distributed, HOST mesh axis spanning
    the process boundary, and check the script's own oracle assertions
    pass (uneven shards + straggler included). ~40 s on one core."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "multihost_run.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    r = subprocess.run([sys.executable, script], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["process_count"] == 2
    assert rec["devices_global"] == 8 and rec["devices_local"] == 4
    assert rec["straggler_observed_wall_s"] >= 1.5
