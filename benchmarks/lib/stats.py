"""The daemon's own counters, read over HTTP as a client would.

``/stats`` gives lines ``name timestamp value tag=value...``; timers
carry ``.count`` and ``.sum_ms``. A layer metric reads differences
between a reading taken at the window's start and one at its end.
"""

from __future__ import annotations

import json

from benchmarks.lib.client import HTTP_TIMEOUT_S, http_get


def get_json(port: int, target: str, timeout: float = HTTP_TIMEOUT_S):
    status, body = http_get(port, target, timeout)
    if status != 200:
        raise RuntimeError(f"GET {target}: HTTP {status}: {body[:300]!r}")
    return json.loads(body)


def parse_stats(lines: list[str]) -> dict[str, float]:
    """-> {"name{tag=v,...}": value}, the per-daemon host tag dropped."""
    out = {}
    for ln in lines:
        w = ln.split()
        tags = ",".join(t for t in w[3:] if not t.startswith("host="))
        out[w[0] + ("{" + tags + "}" if tags else "")] = float(w[2])
    return out


def read_stats(port: int) -> dict[str, float]:
    return parse_stats(get_json(port, "/stats?json"))


def stat_sum(stats: dict[str, float], names: list[str]) -> float:
    """Sum of the entries whose key is one of ``names`` or starts with
    one of them followed by ``{`` (every tag value of that name)."""
    total = 0.0
    for key, v in stats.items():
        base = key.split("{", 1)[0]
        if key in names or base in names:
            total += v
    return total
