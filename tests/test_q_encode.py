"""The body of a /q JSON answer written in fragments (server/qjson.py):
byte for byte what json.dumps of the plain entries gives, with what is
shared formatted once, a kept label's text kept with the label, and
TSDServer._json_output still the seam."""

import asyncio
import gc
import json
import sys

import numpy as np
import pytest

from opentsdb_tpu.obs.registry import METRICS
from opentsdb_tpu.query.grid import KeptTags, QueryResult
from opentsdb_tpu.server import qjson
from opentsdb_tpu.server.tsd import TSDServer

from tests.test_resident_tracing import (
    BASE, SPAN, http_get, make_tsdb, q, serve)

COUNTERS = ("results", "keys.shared", "keys.formatted", "labels.kept",
            "labels.formatted", "plain")


def moved():
    """A callable that gives how far each http.q.encode.* counter moved
    since its last call (or since it was made)."""
    cs = {n: METRICS.counter("http.q.encode." + n) for n in COUNTERS}
    last = {n: c.value for n, c in cs.items()}

    def read():
        was = dict(last)
        last.update({n: c.value for n, c in cs.items()})
        return {n: last[n] - was[n] for n in cs}
    return read


def plain(entries):
    """The entries as they were before the view: dps a dict of boxed
    points, built the way _json_output built it."""
    out = []
    for ent in entries:
        dps = ent["dps"]
        if isinstance(dps, qjson.Dps):
            dps = {str(int(t)): float(v)
                   for t, v in zip(dps.timestamps, dps.values)}
        out.append({**ent, "dps": dps})
    return out


def entries_of(results, plans=None, cached=None, **kw):
    server = TSDServer.__new__(TSDServer)        # just _json_output
    return server._json_output(results, plans, cached, **kw)


def stamps(n, step=3600, base=BASE):
    ts = base + np.arange(n, dtype=np.int64) * step
    ts.flags.writeable = False
    return ts


def grid(groups, points, metric="cpu.usage_user", kept=True, ts=None,
         seed=3):
    """Results as a grid plan hands them out: one timestamps object,
    the values rows of one array, each label one object."""
    ts = stamps(points) if ts is None else ts
    vals = (np.random.default_rng(seed).random((groups, points),
                                               dtype=np.float32)
            .astype(np.float64) * 100)
    out = []
    for g in range(groups):
        agg = ["cpu"] if g % 2 else []
        tags = {"hostname": f"host_{g}", "region": "eu-west-1"}
        out.append(QueryResult(metric, KeptTags(tags, agg) if kept
                               else tags, agg, ts, vals[g]))
    return out


def one(values, ts=None, tags=None, metric="m", agg=()):
    values = np.asarray(values)
    return QueryResult(metric, dict(tags or {}), list(agg),
                       stamps(len(values)) if ts is None
                       else np.asarray(ts), values)


ODD_FLOATS = [57.0, -0.0, 0.0, 1e-07, 1e+16, 1e+15, 123456789012345680.0,
              1.7976931348623157e+308, 5e-324, 2.5, -1.5e-10,
              float(np.float32(51.182163))]


def _cases():
    yield "one_shared_timestamps_object_4000", \
        (grid(4000, 3), ["resident"] * 4000, None, {}), \
        {"keys.shared": 3999, "keys.formatted": 1, "plain": 0}
    per_host = [r._replace(timestamps=r.timestamps.copy())
                for r in grid(4000, 3, kept=False)]
    yield "equal_arrays_different_objects", \
        (per_host, ["raw"] * 4000, None, {}), \
        {"keys.shared": 3999, "keys.formatted": 1, "labels.kept": 0,
         "labels.formatted": 4000}
    ragged = [one(np.arange(n, dtype=np.float64) + 0.5,
                  tags={"host": f"h{i}"})
              for i, n in enumerate([3, 3, 2, 5, 2, 3, 0, 1])]
    yield "arrays_that_differ_within_an_answer", \
        (ragged, ["resident"] * 8, None, {}), \
        {"keys.shared": 3, "keys.formatted": 5, "plain": 0}
    yield "an_empty_dps", ([one([])], ["raw"], None, {}), \
        {"results": 1, "plain": 0}
    yield "odd_floats", ([one(ODD_FLOATS)], None, None, {}), {"plain": 0}
    yield "a_widened_float32_array", \
        ([one(np.array([51.182163, 0.1, 100.0, 3.4028235e38], np.float32))],
         ["resident"], None, {}), {"plain": 0}
    yield "non_finite", \
        ([one([1.5, float("nan"), float("inf"), float("-inf"), -2.0]),
          one([float("-inf")] * 5)], None, None, {}), {"plain": 0}
    yield "integer_values_are_written_as_floats", \
        ([one(np.array([1, -2, 3], np.int64)),
          one(np.array([7, 8], np.int32))], None, None, {}), {"plain": 0}
    yield "float_timestamps_are_cut_to_ints", \
        ([one([1.0, 2.0], ts=np.array([BASE + 0.75, BASE + 60.25]))],
         None, None, {}), {"plain": 0}
    yield "negative_and_32_bit_timestamps", \
        ([one([1.0, 2.0], ts=np.array([-60, 0], np.int32)),
          one([3.0, 4.0], ts=np.array([-60, 0], np.int64))],
         None, None, {}), {"plain": 0, "keys.formatted": 2}
    yield "two_points_of_one_timestamp_are_one_key", \
        ([one([1.0, 2.0, 3.0], ts=[BASE, BASE, BASE + 1])],
         None, None, {}), {"plain": 1}
    yield "more_timestamps_than_values", \
        ([QueryResult("m", {}, [], stamps(3), np.array([1.0, 2.0]))],
         None, None, {}), {"plain": 1}
    yield "odd_tag_values", \
        ([one([1.0], tags={"quote": 'a"b', "back": "a\\b", "ctl": "a\x01\n",
                           "text": "größe-温度-\U0001f600", "": "empty"},
              agg=["z\"", "é"], metric="cpu.\"q\"\\é")],
         ["1h"], [True], {}), {"plain": 0}
    yield "a_metric_that_changes_inside_the_answer", \
        (grid(8, 2, "cpu.a") + grid(8, 2, "cpu.b") + grid(1, 2, "cpu.a"),
         ["resident"] * 8 + ["raw"] * 9, None, {}), {"plain": 0}
    yield "cached_true_and_false", \
        (grid(8, 2), ["raw"] * 8, [True, False, 1, 0, True, True, False,
                                   False], {}), {"plain": 0}
    yield "fewer_plans_and_cached_than_results", \
        (grid(8, 2), ["1h"] * 3, [True], {}), {"plain": 0}
    yield "expert_plan", (grid(8, 2), ["raw"] * 8, None,
                          {"expert": "expert-decline"}), {"plain": 8}
    yield "degraded", (grid(8, 2), ["1h"] * 8, None,
                       {"degraded": "stale,rollup-only"}), {"plain": 8}
    approx = [None, {"kind": "tdigest", "rel_error": 0.01}] * 4
    yield "approx_on_some", (grid(8, 2), ["raw"] * 8, None,
                             {"approx": approx}), {"plain": 4}
    yield "approx_on_none", (grid(8, 2), ["raw"] * 8, None,
                             {"approx": [None] * 8}), {"plain": 0}
    tree = {"name": "query", "ms": 1.5, "spans": [{"name": "x", "ms": 1}]}
    yield "trace_on_the_first_result", \
        (grid(8, 2), ["resident"] * 8, None,
         {"traces": [tree] + [None] * 7}), {"plain": 1}
    yield "no_results", ([], None, None, {}), {"results": 0}
    for n in (1, 8, 4000):
        yield f"{n}_groups_of_13_points", \
            (grid(n, 13), ["resident"] * n, [False] * n, {}), \
            {"results": n, "keys.shared": n - 1, "keys.formatted": 1,
             "labels.formatted": n, "plain": 0}


CASES = {name: (args, counts) for name, args, counts in _cases()}


@pytest.mark.parametrize("name", list(CASES))
def test_body_is_json_dumps_of_the_plain_entries(name):
    (results, plans, cached, kw), counts = CASES[name]
    entries = entries_of(results, plans, cached, **kw)
    assert len(entries) == len(results)
    read = moved()
    body = qjson.encode(entries)
    got = read()
    want = json.dumps(plain(entries)).encode()
    assert body == want
    assert got["results"] == len(results)
    assert {k: got[k] for k in counts} == counts
    # Every entry is counted once: written from its arrays (its keys
    # shared or formatted) or by json.dumps.
    assert (got["keys.shared"] + got["keys.formatted"] + got["plain"]
            >= len(results))
    assert got["labels.kept"] + got["labels.formatted"] + got["plain"] \
        == len(results)


@pytest.mark.parametrize("name", list(CASES))
def test_an_entrys_dps_reads_as_the_dict_it_stands_for(name):
    (results, plans, cached, kw), _ = CASES[name]
    for ent, r in zip(entries_of(results, plans, cached, **kw), results):
        assert list(ent)[:6] == ["metric", "tags", "aggregateTags",
                                 "rollup", "cached", "dps"]
        want = {str(int(t)): float(v)
                for t, v in zip(r.timestamps, r.values)}
        items = list(ent["dps"].items())
        assert all(type(k) is str and type(v) is float for k, v in items)
        assert json.dumps(dict(items)) == json.dumps(want)


def test_the_view_makes_no_copy_of_the_arrays():
    r = grid(1, 5)[0]
    ent, = entries_of([r], ["resident"])
    assert ent["dps"].timestamps is r.timestamps
    assert ent["dps"].values is r.values
    assert ent["tags"] is r.tags
    assert ent["aggregateTags"] is r.aggregated_tags


@pytest.mark.parametrize("replaced", ["dps_a_plain_dict", "a_key_more",
                                      "a_key_less", "keys_in_another_order"])
def test_an_entry_that_is_not_as_json_output_made_it_takes_json_dumps(
        replaced):
    entries = entries_of(grid(8, 2), ["resident"] * 8)
    ent = entries[3]
    if replaced == "dps_a_plain_dict":
        ent["dps"] = {k: v * 2 for k, v in ent["dps"].items()}
    elif replaced == "a_key_more":
        ent["note"] = ["x"]
    elif replaced == "a_key_less":
        del ent["cached"]
    else:
        entries[3] = {k: ent[k] for k in reversed(list(ent))}
    read = moved()
    body = qjson.encode(entries)
    assert body == json.dumps(plain(entries)).encode()
    assert read()["plain"] == 1


def test_an_answer_patched_as_the_benchmarks_control_patches_it(
        monkeypatch):
    """benchmarks/tests/tsd_control.py, answer_off_4e-3: every value of
    every entry 0.4% up, through ``ent["dps"].items()``."""
    render = TSDServer._json_output

    def off(self, *a, **k):
        out = render(self, *a, **k)
        for ent in out:
            ent["dps"] = {t: v * 1.004 for t, v in ent["dps"].items()}
        return out
    results = grid(8, 3)
    honest = json.loads(qjson.encode(entries_of(results, ["resident"] * 8)))
    monkeypatch.setattr(TSDServer, "_json_output", off)
    read = moved()
    body = qjson.encode(entries_of(results, ["resident"] * 8))
    assert read()["plain"] == 8
    for was, ent, r in zip(honest, json.loads(body), results):
        assert list(ent["dps"]) == [str(t) for t in r.timestamps.tolist()]
        assert list(ent["dps"].values()) == [v * 1.004 for v
                                             in was["dps"].values()]
        assert {k: v for k, v in ent.items() if k != "dps"} \
            == {k: v for k, v in was.items() if k != "dps"}


def test_a_kept_labels_text_is_made_once_and_found_again():
    results = grid(8, 3)
    read = moved()
    first = qjson.encode(entries_of(results, ["resident"] * 8))
    got = read()
    assert (got["labels.formatted"], got["labels.kept"]) == (8, 0)
    assert all(r.tags.text is not None for r in results)
    # The plan's next answer: other values, the same label objects.
    again = [r._replace(values=r.values + 1.0) for r in results]
    second = qjson.encode(entries_of(again, ["resident"] * 8))
    got = read()
    assert (got["labels.formatted"], got["labels.kept"]) == (0, 8)
    assert second == json.dumps(plain(entries_of(again,
                                                 ["resident"] * 8))).encode()
    assert first != second
    # A label made on the request is formatted as it comes, every time.
    fresh = grid(8, 3, kept=False)
    for _ in range(2):
        qjson.encode(entries_of(fresh, ["raw"] * 8))
        got = read()
        assert (got["labels.formatted"], got["labels.kept"]) == (8, 0)


def test_a_kept_tags_text_is_for_its_own_aggregated_list_alone():
    r = grid(1, 2)[0]
    qjson.encode(entries_of([r]))
    other = r._replace(aggregated_tags=["hostname"])
    read = moved()
    body = qjson.encode(entries_of([other]))
    assert body == json.dumps(plain(entries_of([other]))).encode()
    assert json.loads(body)[0]["aggregateTags"] == ["hostname"]
    assert read()["labels.kept"] == 0


def test_kept_tags_are_a_dict_to_everything_else():
    tags = KeptTags({"host": "a"}, ["cpu"])
    assert tags == {"host": "a"} and isinstance(tags, dict)
    assert json.dumps(tags) == '{"host": "a"}'
    assert tags.text is None and tags.aggregated == ["cpu"]
    assert not hasattr(tags, "__dict__")


class TestServed:
    """Through the daemon's /q handler, on a resident store."""

    M = "max:5m-max:res.cpu{host=*}"

    def test_the_plans_next_answer_finds_its_labels_text(self, tmp_path):
        tsdb = make_tsdb(tmp_path, hosts=8)
        end = BASE + SPAN - 10
        read = moved()
        seen = []
        got = serve(tsdb,
                    q(BASE, end, self.M, trace=False),
                    lambda: seen.append(read()),
                    q(BASE + 600, end, self.M, trace=False),
                    lambda: seen.append(read()),
                    q(BASE, end, self.M, "max:5m-max:res.mem{host=*}",
                      trace=False),
                    lambda: seen.append(read()),
                    "/stats")
        assert [st for st, _ in (got[0], got[2], got[4])] == [200] * 3
        for _, body in (got[0], got[2]):
            ents = json.loads(body)
            assert len(ents) == 8
            assert {e["rollup"] for e in ents} == {"resident"}
        first, second, third = seen
        assert first == {"results": 8, "keys.shared": 7,
                         "keys.formatted": 1, "labels.kept": 0,
                         "labels.formatted": 8, "plain": 0}
        assert second == {"results": 8, "keys.shared": 7,
                          "keys.formatted": 1, "labels.kept": 8,
                          "labels.formatted": 0, "plain": 0}
        # Two sub-queries: a run of keys each (the second's array is
        # another object of the same bytes), res.cpu's labels found,
        # res.mem's plan answering for the first time.
        assert third == {"results": 16, "keys.shared": 15,
                         "keys.formatted": 1, "labels.kept": 8,
                         "labels.formatted": 8, "plain": 0}
        listed = {ln.split()[0] for ln in got[6][1].decode().splitlines()}
        assert {"tsd.http.q.encode." + n for n in COUNTERS} <= listed

    def test_the_served_body_is_json_dumps_of_its_own_parse(self, tmp_path):
        tsdb = make_tsdb(tmp_path, hosts=8)
        end = BASE + SPAN - 10
        got = serve(tsdb, q(BASE, end, self.M, trace=False),
                    q(BASE, end, self.M, trace=False),
                    q(BASE, end, self.M, trace=True))
        for st, body in got:
            assert st == 200
            assert json.dumps(json.loads(body)).encode() == body
        assert got[0][1] == got[1][1]
        traced = json.loads(got[2][1])
        assert "trace" in traced[0] and "trace" not in traced[1]

    def test_the_text_goes_when_the_plan_leaves_its_cache(self, tmp_path):
        tsdb = make_tsdb(tmp_path, hosts=8)
        end = BASE + SPAN - 10
        server = TSDServer(tsdb)
        cache = server.executor.resident.plan_cache
        held = []

        async def main():
            await server.start()
            try:
                st, _ = await http_get(server.port,
                                       q(BASE, end, self.M, trace=False))
                assert st == 200
            finally:
                server.selfmon.stop()
                server._pool.shutdown(wait=False)
                server._server.close()
                await server._server.wait_closed()
        asyncio.run(main())
        key, = cache.keys()
        held.extend(tags for tags, _agg in cache.peek(key)[-1].labels)
        assert len(held) == 8
        assert all(type(t) is KeptTags and t.text is not None
                   for t in held)
        gc.collect()
        # A label is held by its plan and by this test, its text by the
        # label alone (getrefcount counts its own argument too).
        assert {sys.getrefcount(held[i]) for i in range(8)} == {3}
        assert {sys.getrefcount(held[i].text) for i in range(8)} == {2}
        cache.clear()
        gc.collect()
        # The plan gone, nothing else holds a label or its text: no
        # table beside the plan, by id() or otherwise.
        assert {sys.getrefcount(held[i]) for i in range(8)} == {2}
        assert {sys.getrefcount(held[i].text) for i in range(8)} == {2}
