"""The deployment's data, from the seed, and the plain reference.

One generator serves everything that needs the data: the store builder
(which writes it through the program's columnar API), the collectors of
a load cell (which send it as telnet ``put`` lines) and the check (which
computes what the daemon must answer). Nothing here imports jax or the
program.

A deployment is described by its config file (``benchmarks/configs``):
``hosts`` hosts report ``metrics`` gauges every ``interval_s`` seconds
from ``t0`` on, each point tagged with the host's ``tags``.

Values: every (metric, host) series is a random walk clamped to
[0, 100] at every step (TSBS's ClampedRandomWalkDistribution), kept in
hundredths of a percent as integers, so that ``4237`` is sent as the
text ``42.37`` and stored by the daemon as the float32 nearest to it.
The walk is generated time-major, so the first ``k`` steps are the same
whatever the total number of steps asked for: a load cell's collectors
continue exactly where the loaded store ends.
"""

from __future__ import annotations

import json
import os

import numpy as np

REGIONS = ("us-east-1", "us-west-1", "us-west-2", "eu-west-1",
           "eu-central-1", "ap-southeast-1", "ap-southeast-2",
           "ap-northeast-1", "sa-east-1")
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
WALK_SIGMA = 100.0              # one percent a step, in hundredths
# Salts that keep the random streams of one seed apart.
_TAG_STREAM, _VALUE_STREAM = 1, 2
# What a config's optional ``store`` object may say of how its store is
# built (``lib/store.py`` hands it to the program's ``Config``).
STORE_KEYS = ("sstable_codec",)


def rng(seed: int, *stream: int) -> np.random.Generator:
    """The generator of one stream of ``seed`` (any whole number; it is
    split so that one over 32 bits seeds as well as a small one)."""
    return np.random.default_rng([seed % (1 << 32), seed >> 32, *stream])


def load_config(path: str) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    for key in ("hosts", "interval_s", "hours", "t0", "metrics", "tags"):
        if key not in cfg:
            raise ValueError(f"{path}: config lacks {key!r}")
    unknown = sorted(set(cfg.get("store", ())) - set(STORE_KEYS))
    if unknown:
        raise ValueError(f"{path}: 'store' has {unknown}; the builder "
                         f"knows {list(STORE_KEYS)}")
    return cfg


def loaded_steps(cfg: dict) -> int:
    return int(round(cfg["hours"] * 3600)) // int(cfg["interval_s"])


def host_tag_table(cfg: dict, seed: int) -> list[dict[str, str]]:
    """Per host, the tag map: ``host`` is the index, ``region`` and
    ``datacenter`` follow it, the rest are drawn once from the seed (a
    host keeps its tags for life, as in TSBS)."""
    hosts = int(cfg["hosts"])
    draw = rng(seed, _TAG_STREAM)
    rack = draw.integers(0, 100, hosts)
    osi = draw.integers(0, len(OSES), hosts)
    arch = draw.integers(0, len(ARCHES), hosts)
    team = draw.integers(0, len(TEAMS), hosts)
    service = draw.integers(0, 20, hosts)
    table = []
    for h in range(hosts):
        region = REGIONS[h % len(REGIONS)]
        full = {
            "host": f"host_{h}",
            "region": region,
            "datacenter": region + "abc"[(h // len(REGIONS)) % 3],
            "rack": str(int(rack[h])),
            "os": OSES[osi[h]],
            "arch": ARCHES[arch[h]],
            "team": TEAMS[team[h]],
            "service": str(int(service[h])),
        }
        table.append({k: full[k] for k in cfg["tags"]})
    return table


def metric_values(cfg: dict, seed: int, metric: int,
                  steps: int) -> np.ndarray:
    """[steps, hosts] int32 hundredths for metric number ``metric``."""
    n = int(cfg["hosts"])
    draw = rng(seed, _VALUE_STREAM, metric)
    state = draw.integers(0, 10001, n).astype(np.float64)
    out = np.empty((steps, n), np.int32)
    # Drawn in blocks of steps, so that memory stays small for a long
    # span and the stream is the same whatever ``steps`` is.
    block = 1024
    for s0 in range(0, steps, block):
        inc = draw.normal(0.0, WALK_SIGMA, (block, n))
        for k in range(min(block, steps - s0)):
            state += inc[k]
            np.clip(state, 0.0, 10000.0, out=state)
            out[s0 + k] = np.rint(state)
    return out


def stored(values_int: np.ndarray) -> np.ndarray:
    """What the daemon holds for the text ``dd.dd``: the float32 nearest
    to it, as float64."""
    return (values_int / 100.0).astype(np.float32).astype(np.float64)


def gauge_text() -> list[bytes]:
    return [b"%d.%02d" % divmod(v, 100) for v in range(10001)]


# ---------------------------------------------------------------------------
# The plain reference: OpenTSDB 1.x downsample + group-by in numpy
# float64, generic over (aggregator, downsample, interval, group-by tag,
# filter), so that a new query type is a line of a traffic file.
# ---------------------------------------------------------------------------

_DOWN = {
    "avg": lambda v, idx, n: (np.add.reduceat(v, idx, axis=0)
                              / n[:, None]),
    "sum": lambda v, idx, n: np.add.reduceat(v, idx, axis=0),
    "max": lambda v, idx, n: np.maximum.reduceat(v, idx, axis=0),
    "min": lambda v, idx, n: np.minimum.reduceat(v, idx, axis=0),
    "count": lambda v, idx, n: np.repeat(n[:, None].astype(np.float64),
                                         v.shape[1], axis=1),
}
_GROUP = {
    "sum": lambda g: g.sum(axis=1),
    "avg": lambda g: g.mean(axis=1),
    "max": lambda g: g.max(axis=1),
    "min": lambda g: g.min(axis=1),
}
EXACT_AGGS = ("max", "min", "count")


def parse_m(m: str) -> dict:
    """``agg:[interval-dsagg:]metric[{tag=value|value|*}]`` (the subset
    of the 1.x grammar that the traffic files use)."""
    tags: dict[str, str] = {}
    if m.endswith("}"):
        m, _, inner = m[:-1].partition("{")
        for pair in inner.split(","):
            k, _, v = pair.partition("=")
            tags[k] = v
    parts = m.split(":")
    if len(parts) == 2:
        agg, metric = parts
        down = None
    elif len(parts) == 3:
        agg, ds, metric = parts
        span, _, dsagg = ds.partition("-")
        unit = {"s": 1, "m": 60, "h": 3600, "d": 86400}[span[-1]]
        down = (int(span[:-1]) * unit, dsagg)
    else:
        raise ValueError(f"reference cannot read m={m!r}")
    return {"agg": agg, "down": down, "metric": metric, "tags": tags}


def reference(cfg: dict, tag_table: list[dict[str, str]],
              values: np.ndarray, m: dict, start: int, end: int) -> dict:
    """Expected answer of one ``m=`` sub-query over ``values``
    ([steps, hosts] int hundredths, as ``metric_values`` makes them;
    the arithmetic is float64 over what the daemon stores of them): a
    dict from the group's tag items (sorted tuple) to (timestamps,
    values).

    Semantics held to: points with start <= ts <= end; buckets aligned
    to the epoch (``ts - ts % interval``); a ``tag=a|b`` or ``tag=*``
    filter groups by that tag, a ``tag=a`` filter only selects; the
    series of a group are combined bucket by bucket. Every series of
    this data has a point at every step, so no interpolation arises;
    the function refuses a window in which that would not hold."""
    step, t0 = int(cfg["interval_s"]), int(cfg["t0"])
    steps = values.shape[0]
    first = max(0, -(-(start - t0) // step))
    last = min(steps - 1, (end - t0) // step)
    if last < first:
        return {}
    ts = t0 + step * np.arange(first, last + 1, dtype=np.int64)
    keep = np.ones(len(tag_table), bool)
    group_by = []
    for k, v in m["tags"].items():
        if v == "*" or "|" in v:
            group_by.append(k)
        if v != "*":
            allowed = set(v.split("|"))
            keep &= np.array([t.get(k) in allowed for t in tag_table])
    cols = np.flatnonzero(keep)
    if cols.size == 0:
        return {}
    v = stored(values[first:last + 1][:, cols])
    if m["down"] is None:
        out_ts, grid = ts, v
    else:
        interval, dsagg = m["down"]
        bucket = ts - ts % interval
        idx = np.flatnonzero(np.r_[True, np.diff(bucket) != 0])
        n = np.diff(np.r_[idx, len(ts)])
        out_ts, grid = bucket[idx], _DOWN[dsagg](v, idx, n)
    groups: dict[tuple, list[int]] = {}
    for j, c in enumerate(cols):
        key = tuple(sorted((k, tag_table[c][k]) for k in group_by))
        groups.setdefault(key, []).append(j)
    out = {}
    for key, members in groups.items():
        g = grid[:, members]
        vals = g[:, 0] if len(members) == 1 else _GROUP[m["agg"]](g)
        out[key] = (out_ts, vals)
    return out


def compare(got_dps: dict, want_ts, want_vals, rtol: float) -> float:
    """The largest relative error of a result's ``dps`` against the
    expected series, or inf where the timestamps differ. ``rtol`` 0
    asks for equality, and then any difference is inf."""
    if len(got_dps) != len(want_ts):
        return float("inf")
    worst = 0.0
    for t, w in zip(want_ts, want_vals):
        g = got_dps.get(str(int(t)))
        if g is None:
            return float("inf")
        w = float(w)
        if g == w:
            continue
        if rtol == 0.0:
            return float("inf")
        worst = max(worst, abs(g - w) / max(abs(w), 1e-30))
    return worst


def find_file(bench_root: str, kind: str, name: str) -> str:
    """``benchmarks/<kind>/<name>.<ext>``: how the harness finds a mix or
    a layer metric by the name ``BENCHMARK.json`` gives it."""
    for ext in (".json", ".jsonl", ".toml", ".txt", ".csv"):
        path = os.path.join(bench_root, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind} file named {name!r} under "
                            f"{os.path.join(bench_root, kind)}")
