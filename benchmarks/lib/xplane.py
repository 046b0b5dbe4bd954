"""Reduce a profiler trace (``*.xplane.pb``) to the device figures.

Run as a child with ``JAX_PLATFORMS=cpu`` once the daemon is gone
(``python -m benchmarks.lib.xplane <trace dir> [<from s> <to s>]``, the
bounds on the profile's own clock: only what ran between them counts.
The profiler records for a second or so after it is told to stop, and
a span that ends amid traffic would count that as busy): reading the file
needs ``jax.profiler.ProfileData``, and the harness's parent never
imports jax. Prints one JSON object:

- ``busy_s``: per device plane, the union of the intervals in which an
  operation ran (the ``XLA Ops`` line), averaged over the planes;
- ``span_s``: first operation's start to last operation's end;
- ``device_ops``: the operations that took most time, ``[name, s]``;
- ``idle_gaps``: the longest gaps between operations. The program
  writes no ``TraceAnnotation`` yet, so a gap is named by the operation
  that ended it, not by what the host was doing.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def short_name(event_name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``: the trace
    names an operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def union(intervals: list[tuple[int, int, str]]):
    """Merged busy intervals and the gaps between them, from
    (start_ns, end_ns, name) sorted by start."""
    busy, gaps = [], []
    cur_s = cur_e = None
    for s, e, name in intervals:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy.append((cur_s, cur_e))
            gaps.append((s - cur_e, name))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy.append((cur_s, cur_e))
    return busy, gaps


def reduce_planes(planes, clip: tuple[int, int] | None = None) -> dict:
    """``planes``: iterable of (plane name, [(line name, [(name,
    start_ns, duration_ns)])]) — what ``ProfileData`` holds, as plain
    tuples so that a test can hand-make one. ``clip``: (from_ns, to_ns)
    on the events' own clock; an operation counts by its part inside."""
    per_plane, ops, gaps_all = [], {}, []
    first, last = None, None
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        chosen = [ln for ln in lines if ln[0] == OPS_LINE] or lines
        iv = sorted((s, s + d, short_name(n)) for _ln, evs in chosen
                    for n, s, d in evs if d > 0)
        if clip is not None:
            iv = [(max(s, clip[0]), min(e, clip[1]), n) for s, e, n in iv
                  if min(e, clip[1]) > max(s, clip[0])]
        for s, e, n in iv:
            ops[n] = ops.get(n, 0) + (e - s)
        busy, gaps = union(iv)
        per_plane.append(sum(e - s for s, e in busy))
        gaps_all += gaps
        if busy:
            first = busy[0][0] if first is None else min(first, busy[0][0])
            last = busy[-1][1] if last is None else max(last, busy[-1][1])
    n = len(per_plane)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps_all, key=lambda g: -g[0])[:TOP]
    return {
        "device_planes": n,
        "busy_s": (sum(per_plane) / n / 1e9) if n else 0.0,
        "span_s": ((last - first) / 1e9) if n and first is not None else 0.0,
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
        "idle_gaps": [["before " + name, ns / 1e9] for ns, name in top_gaps],
    }


def read_planes(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        yield plane.name, [
            (line.name, [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                         for ev in line.events])
            for line in plane.lines]


def main(argv: list[str]) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    clip = ((int(float(argv[1]) * 1e9), int(float(argv[2]) * 1e9))
            if len(argv) > 2 else None)
    out = reduce_planes(read_planes(path), clip)
    out["file_bytes"] = os.path.getsize(path)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
