"""The planner's list of plans (query/executor.py, ``_run_planned``):
what it tries and in which order, that a plan leaves the list as one
object, that the resident plan builds a stage one way for one shard
and for several, and which module may know which."""

import ast
import os
import re

import numpy as np
import pytest

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import METRICS
from opentsdb_tpu.ops import kernels
from opentsdb_tpu.query import grid as qgrid
from opentsdb_tpu.query.aggregators import Aggregators
from opentsdb_tpu.query.executor import QueryExecutor, QuerySpec
from opentsdb_tpu.storage.kv import MemKVStore
from opentsdb_tpu.utils.config import Config

BASE = 1356998400
SPAN = 6 * 3600
HOSTS = 6
QUERY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "opentsdb_tpu", "query")


def make_tsdb(path, **over):
    """Six hosts of integer values, 10 s apart over six hours."""
    os.makedirs(path, exist_ok=True)
    kw = dict(auto_create_metrics=True, wal_path=str(path), shards=1,
              backend="tpu", enable_sketches=False, device_window=False,
              enable_rollups=False)
    kw.update(over)
    tsdb = TSDB(MemKVStore(wal_path=os.path.join(path, "wal")),
                Config(**kw), start_compaction_thread=False)
    rng = np.random.default_rng(47)
    ts = BASE + np.arange(0, SPAN, 10, dtype=np.int64)
    for i in range(HOSTS):
        tsdb.add_batch("plan.cpu", ts, rng.integers(0, 1000, len(ts)),
                       {"host": f"h{i}", "dc": f"d{i % 2}"})
    return tsdb


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    """The same points four ways: in the device window (and, for the
    sharded window, over three shards), in TSST4 blocks, under rollup
    tiers, and in a plain store."""
    root = tmp_path_factory.mktemp("plans")
    made = {
        "window": make_tsdb(str(root / "w"), device_window=True),
        "shards": make_tsdb(str(root / "s"), device_window=True,
                            devwindow_shards=3),
        "blocks": make_tsdb(str(root / "b"), sstable_codec="tsst4"),
        "tiers": make_tsdb(str(root / "t"), enable_rollups=True,
                           rollup_catchup="sync"),
        "plain": make_tsdb(str(root / "p")),
    }
    for name in ("blocks", "tiers"):
        made[name].checkpoint()
    yield made
    for tsdb in made.values():
        tsdb.shutdown()


def spec_of(agg="max", dsagg="max", interval=300, tags=None):
    return QuerySpec("plan.cpu", {"host": "*"} if tags is None else tags,
                     agg, downsample=(interval, dsagg) if dsagg else None)


RANGE = (BASE + 7, BASE + SPAN - 11)
# (store, request, the label run_with_plan returns, the plans whose
# serve was called, in order).
SERVED = [
    ("window", spec_of(), "resident", ["resident"]),
    ("window", spec_of("p95", "avg"), "resident", ["resident"]),
    ("shards", spec_of("sum", "avg"), "resident", ["resident"]),
    ("window", spec_of(dsagg=None), "raw", ["resident", "fused"]),
    ("blocks", spec_of(), "fused", ["resident", "fused"]),
    ("blocks", spec_of("sum", "avg", tags={"dc": "d1"}), "fused",
     ["resident", "fused"]),
    ("tiers", spec_of("sum", "sum", 3600, tags={}), "1h", ["resident"]),
    ("plain", spec_of(), "raw", ["resident", "fused"]),
]


def watched(ex):
    """The labels of the plans whose ``serve`` is called from now on."""
    tried = []
    for plan in ex.plans:
        def serve(*a, _serve=plan.serve, _label=plan.label):
            tried.append(_label)
            return _serve(*a)
        plan.serve = serve
    return tried


@pytest.mark.parametrize("store,spec,label,order", SERVED,
                         ids=[f"{s}-{l}-{n}" for n, (s, _q, l, _o)
                              in enumerate(SERVED)])
def test_the_label_and_the_order_the_plans_are_tried_in(stores, store, spec,
                                                        label, order):
    ex = QueryExecutor(stores[store], backend="tpu")
    assert [p.label for p in ex.plans] == ["resident", "fused"]
    assert ex.plans == [ex.resident, ex.fused]
    tried = watched(ex)
    trace = obs_trace.Trace("q")
    results, plan, _cached = ex.run_with_plan(spec, *RANGE, trace=trace)
    assert results and plan == label and tried == order
    (pick,) = [s for s in trace.root.children if s.name == "planner.pick"]
    assert pick.tags["plan"] == label
    # A plan's spans lie under the one planner.pick, and carry its label.
    under = {c.name.split(".")[0] for c in pick.children}
    assert under <= {label, "rollup", "raw"}, under


def same_bytes(a, b):
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.metric, x.tags, x.aggregated_tags) == (
            y.metric, y.tags, y.aggregated_tags)
        assert x.timestamps.tobytes() == y.timestamps.tobytes()
        assert x.values.tobytes() == y.values.tobytes()


@pytest.mark.parametrize("store,gone,first,then", [
    ("window", "resident", "resident", "raw"),
    ("shards", "resident", "resident", "raw"),
    ("blocks", "fused", "fused", "raw"),
    ("blocks", "resident", "fused", "fused"),
])
def test_a_plan_leaves_the_list_as_one_object(stores, store, gone, first,
                                              then):
    """An executor without one of its plans answers the same request
    with the same bytes from the next plan (max of max of integers: no
    float32 sum to reassociate)."""
    whole = QueryExecutor(stores[store], backend="tpu")
    fewer = QueryExecutor(stores[store], backend="tpu")
    fewer.plans.remove(getattr(fewer, gone))
    for spec in (spec_of(), spec_of(tags={"host": "h1|h4", "dc": "*"})):
        want, plan, _c = whole.run_with_plan(spec, *RANGE)
        assert plan == first
        got, plan, _c = fewer.run_with_plan(spec, *RANGE)
        assert plan == then
        same_bytes(got, want)


def test_a_shed_request_is_served_by_no_plan_that_reads_storage(stores):
    """rollup_only (the load-shedding ladder's step): the plans that
    read no storage and the tiers are tried, and a request they cannot
    serve is a 503 before a plan that reads blocks is asked."""
    from opentsdb_tpu.core.errors import OverloadedError
    ex = QueryExecutor(stores["blocks"], backend="tpu")
    assert [p.storage_free for p in ex.plans] == [True, False]
    tried = watched(ex)
    with pytest.raises(OverloadedError):
        ex.run_with_plan(spec_of(), *RANGE, rollup_only=True)
    assert tried == ["resident"]
    ex = QueryExecutor(stores["window"], backend="tpu")
    _r, plan, _c = ex.run_with_plan(spec_of(), *RANGE, rollup_only=True)
    assert plan == "resident"


SHARD_STATS = ("devwindow.stage.shards", "mesh.resident.gather.bytes")


def span_names(span):
    yield span.name
    for child in span.children:
        yield from span_names(child)


@pytest.mark.parametrize("dsagg,rate", [("max", False), ("avg", False),
                                        ("sum", True)])
def test_one_window_is_its_own_single_shard(stores, monkeypatch, dsagg,
                                            rate):
    """A plain DeviceWindow through the one stage builder: the grids are
    the bytes of the call the resident plan made for it before it had
    one builder (the window's chunks folded whole into grids S_pad
    high, on the default placement), no join runs and nothing says
    there was a shard."""
    tsdb = stores["window"]
    ex = QueryExecutor(tsdb, backend="tpu")
    start, end = RANGE
    interval = 300
    uid = tsdb.metrics.get_id("plan.cpu")
    cols = tsdb.devwindow.chunk_columns(uid, start, end)
    assert cols.shards == [cols] and cols.window is tsdb.devwindow
    assert tsdb.devwindow.n_shards == 1
    spec = spec_of("sum", dsagg)._replace(rate=rate)
    rate_kw = qgrid.rate_kw(spec)
    qbase = start - start % interval
    num_buckets = qgrid._pad_size((end - qbase) // interval + 1)
    S_pad = qgrid._pad_size(len(cols.series_keys))
    want = kernels.window_series_stage_chunks(
        cols.chunks, np.int32(start - cols.epoch),
        np.int32(end - cols.epoch), np.int32(qbase - cols.epoch),
        num_series=S_pad, num_buckets=num_buckets, interval=interval,
        agg_down=dsagg, blocks=cols.blocks, block=cols.block, **rate_kw)
    joins = []
    monkeypatch.setattr(kernels, "shard_combine",
                        lambda *a: joins.append(a))
    before = [METRICS.counter(n).value for n in SHARD_STATS]
    got = ex.resident._stage(
        (tsdb.devwindow.instance_id, uid), cols, start, end, qbase,
        num_buckets=num_buckets, S_pad=S_pad, interval=interval,
        dsagg=dsagg, rate_kw=rate_kw)
    assert len(got) == 5
    for g, w in zip(got, want):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
    trace = obs_trace.Trace("q")
    _r, plan, _c = ex.run_with_plan(spec, start, end, trace=trace)
    assert plan == "resident"
    names = set(span_names(trace.root))
    assert "resident.stage" in names
    assert not names & {"resident.shard", "resident.gather"}
    assert not joins and not len(ex.resident.shard_warm)
    assert [METRICS.counter(n).value for n in SHARD_STATS] == before


def test_several_shards_are_folded_each_and_joined(stores):
    """The same request over the window of three shards: a
    resident.shard a live shard and one resident.gather under
    resident.stage, counted, and the plain window's answer."""
    spec = spec_of()
    ex = QueryExecutor(stores["shards"], backend="tpu")
    before = METRICS.counter(SHARD_STATS[0]).value
    trace = obs_trace.Trace("q")
    got, plan, _c = ex.run_with_plan(spec, *RANGE, trace=trace)
    assert plan == "resident"
    (stage,) = [s for s in trace.root.children[0].children
                if s.name == "resident.stage"]
    kids = [c.name for c in stage.children]
    live = len(kids) - 1
    assert 1 < live <= 3
    assert kids == ["resident.shard"] * live + ["resident.gather"]
    assert METRICS.counter(SHARD_STATS[0]).value - before == live
    want = QueryExecutor(stores["window"], backend="tpu").run(spec, *RANGE)
    same_bytes(sorted(got, key=lambda r: r.tags["host"]),
               sorted(want, key=lambda r: r.tags["host"]))


def test_a_mesh_executor_declines_a_window_of_one_shard(stores):
    """Told from the window's shard count, not from its type."""
    agg = Aggregators.get("max")
    for store, served in (("window", False), ("shards", True)):
        ex = QueryExecutor(stores[store], backend="tpu", mesh=object())
        assert (ex.resident.serve(spec_of(), *RANGE, agg)
                is not None) == served


def imports_of(path):
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("module", ["grid", "resident", "fused"])
def test_a_plan_module_does_not_import_the_executor(module):
    """Arrows one way: executor.py imports the plans."""
    found = list(imports_of(os.path.join(QUERY, module + ".py")))
    assert found and not [m for m in found if "executor" in m]
    assert any(m.startswith(f"opentsdb_tpu.query.{module}")
               for m in imports_of(os.path.join(QUERY, "executor.py")))


def test_the_executor_names_no_plans_internals():
    """No cache, counter or kernel of the resident or the fused plan in
    executor.py, and no test of a window's type in the package."""
    source = open(os.path.join(QUERY, "executor.py")).read()
    assert not re.findall(r"\b(?:_dw_|_fused_|_devcache)\w*", source)
    for name in ("window_series_stage_chunks", "shard_combine",
                 "window_moment_apply", "window_quantile_apply",
                 "slab_stage", "devwindow.", "compress.fused"):
        assert name not in source, name
    ex_lines = source.count("\n")
    assert ex_lines < 1950, ex_lines
    for name in os.listdir(QUERY):
        if name.endswith(".py"):
            text = open(os.path.join(QUERY, name)).read()
            assert 'hasattr(dw, "shard_of")' not in text, name
            assert not re.search(r"\bif sharded\b", text), name
