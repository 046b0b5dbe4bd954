"""The readers that turn what a traced run gathered into layer metrics.

A layer metric is a file ``benchmarks/layers/<name>.json`` that names
one reader here and its arguments. The harness evaluates the metrics
``BENCHMARK.json`` lists for the cell; a reader that finds nothing to
read returns None and the metric is left out of the line.

What a reader is given (``ctx``): ``kind`` (the traffic's), ``done``
(the window's requests, each with ``ms``, ``type``, ``spans``,
``results``, ``resident``, ``cached``, ``t_wall_end``,
``series_steps``), ``before`` / ``after`` (``/stats`` as read at the
window's ends), ``window_s``, ``points`` (points acknowledged in the
window), ``compiles`` (programs the compile cache gained in the
window), ``trace`` (the reduced profiler trace and the wall-clock
bounds of the span it was recorded over), ``device_kind``. The readers
of requests take those answered inside the traced span, the readers of
``/stats`` the whole window.
"""

from __future__ import annotations

import statistics

from benchmarks.lib import roofline, stats, tsbs

# What a window of a kind of traffic gives a reader, where that is more
# than its own kind's: a live window holds requests and acknowledged
# points, so the metrics of both kinds read in it. A layer file's
# ``kinds`` never names ``live``.
READS_AS = {"live": ("queries", "load")}


def _span_ms(tree: dict, name: str) -> float | None:
    """Time under the spans of this name in one tree; None where the
    tree holds none."""
    total = tree["ms"] if tree.get("name") == name else None
    for child in tree.get("spans", ()):
        sub = _span_ms(child, name)
        if sub is not None:
            total = (total or 0.0) + sub
    return total


def _request_span_ms(done, name: str) -> float | None:
    found = [ms for ms in (_span_ms(t, name) for t in done.spans)
             if ms is not None]
    return sum(found) if found else None


def _typed(ctx, args):
    """The window's answered requests of the reader's types: those
    answered before the profiler stopped. The profile is written beside
    the rest of the window and slows its requests (cpu100.dash-12h:
    q_p50_ms 9% up, PERF.md section 5), which is the method's cost and
    not the program's."""
    prefix = args.get("type_prefix", "")
    done = [d for d in ctx.get("done", ()) if d.ok
            and d.req.type.startswith(prefix)]
    stop = (ctx.get("trace") or {}).get("t_stop")
    return done if stop is None else [d for d in done
                                      if d.t_wall_end <= stop]


def client_median(ctx, args):
    ms = [d.ms for d in _typed(ctx, args)]
    return statistics.median(ms) if ms else None


def client_percentile(ctx, args):
    ms = sorted(d.ms for d in _typed(ctx, args))
    if not ms:
        return None
    # Linear interpolation between closest ranks, as numpy's default.
    pos = (len(ms) - 1) * float(args["q"]) / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(ms) - 1)
    return ms[lo] + (ms[hi] - ms[lo]) * (pos - lo)


def span_median(ctx, args):
    """Per request, the time under spans of this name (summed over its
    sub-queries); the median over requests."""
    vals = [ms for ms in (_request_span_ms(d, args["span"])
                          for d in _typed(ctx, args)) if ms is not None]
    return statistics.median(vals) if vals else None


def client_minus_span_median(ctx, args):
    vals = [d.ms - ms for d, ms in (
        (d, _request_span_ms(d, args["span"])) for d in _typed(ctx, args))
        if ms is not None]
    return statistics.median(vals) if vals else None


def result_share(ctx, args):
    done = _typed(ctx, args)
    total = sum(d.results for d in done)
    if not total:
        return None
    return 100.0 * sum(getattr(d, args["field"]) for d in done) / total


def _delta(ctx, names):
    return (stats.stat_sum(ctx["after"], names)
            - stats.stat_sum(ctx["before"], names))


def stats_ratio(ctx, args):
    """Delta of the named /stats entries over the window, divided by
    ``per``: ``kpoints`` (thousands of points acknowledged),
    ``window_ms``, or 1."""
    if "after" not in ctx:
        return None
    num = _delta(ctx, args["names"])
    per = args.get("per", "one")
    if per == "kpoints":
        den = ctx.get("points", 0) / 1000.0
    elif per == "window_ms":
        den = ctx["window_s"] * 1000.0
    else:
        den = 1.0
    if den <= 0:
        return None
    return args.get("scale", 1.0) * num / den


def stats_share_at_end(ctx, args):
    if "after" not in ctx:
        return None
    den = stats.stat_sum(ctx["after"], args["of"])
    if den <= 0:
        return None
    return 100.0 * stats.stat_sum(ctx["after"],
                                  args["names"]) / den


def compiles(ctx, args):
    return ctx.get("compiles")


def _traced_requests(ctx):
    """The window's requests that overlap the traced span, each with the
    share of its own time that lies inside it: a request half inside
    counts as half a request, and brings half of what it needs."""
    tr = ctx.get("trace")
    if not tr or tr.get("busy_s", 0) <= 0:
        return None, []
    inside = []
    for d in ctx.get("done", ()):
        t1 = d.t_wall_end
        t0 = t1 - d.ms / 1000.0
        both = min(t1, tr["t_stop"]) - max(t0, tr["t_start"])
        if both > 0 and t1 > t0:
            inside.append((d, both / (t1 - t0)))
    return tr, inside


def trace_busy_ms_per_request(ctx, args):
    tr, inside = _traced_requests(ctx)
    if not inside:
        return None
    return 1000.0 * tr["busy_s"] / sum(w for _d, w in inside)


def trace_hbm_share(ctx, args):
    tr, inside = _traced_requests(ctx)
    if not inside:
        return None
    steps = sum(w * d.req.series_steps for d, w in inside)
    return roofline.hbm_share_pct(steps, tr["busy_s"], ctx["device_kind"])


READERS = {f.__name__: f for f in (
    client_median, client_percentile, span_median, client_minus_span_median, result_share,
    stats_ratio, stats_share_at_end, compiles, trace_busy_ms_per_request,
    trace_hbm_share)}


def find(bench_root: str, name: str) -> str:
    """The file of a per-layer metric: ``layers/<name>.json``. A quantity
    whose cells report different end-to-end metrics is split in
    ``BENCHMARK.json`` (``parse_ms_per_kpt.live`` moves ``q_mean_ms``
    where ``parse_ms_per_kpt`` moves ``ingest_points_per_s``); a split
    name with no file of its own is read by its quantity's."""
    try:
        return tsbs.find_file(bench_root, "layers", name)
    except FileNotFoundError:
        if "." not in name:
            raise
        return tsbs.find_file(bench_root, "layers", name.rsplit(".", 1)[0])


def evaluate(layer: dict, ctx: dict):
    reader = READERS.get(layer["reader"])
    if reader is None:
        raise KeyError(f"layer metric {layer['name']!r} names reader "
                       f"{layer['reader']!r}; known: {sorted(READERS)}")
    if not set(READS_AS.get(ctx["kind"], (ctx["kind"],))) \
            & set(layer["kinds"]):
        return None
    return reader(ctx, layer.get("args", {}))
