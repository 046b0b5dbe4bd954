"""What the host was doing while the device sat idle, and the device's
time by the plan that ran it: the two readings of a profiler trace that
need the program's own names in it.

Run once the daemon is gone (``python -m benchmarks.lib.hostgaps <trace
dir>``), like ``lib/xplane.py``; ``run.py`` does not call it. Prints one
JSON object:

- ``gaps``: the ten longest gaps between operations on the device's
  ``XLA Ops`` line, longest first. Each has its length ``s``, the
  operation that ended it (``before``), ``host``: the program's host
  annotations that were open in it with the seconds of the gap each
  covers, and ``unattributed_s``: the seconds in which none was. Host
  threads run side by side (one encodes while another waits for the
  GIL to dispatch), so the names' seconds may add up to more than the
  gap; ``unattributed_s`` is exact.
- ``gap_s`` / ``attributed_s``: those ten gaps' seconds, and the part
  of them some annotation covers.
- ``device_by_plan``: device time summed by the ``ExecPlan`` name the
  operation was compiled under (``parallel/compile.py`` wraps every
  body in ``jax.named_scope(plan.name)``; the profiler keeps that scope
  in the ``tf_op`` stat of the operation's *event metadata*, as
  ``jit(_chunk_fold)/window.chunk_fold/scatter-max:``). A copy the
  compiler inserted carries no scope and goes to the plan the rest of
  its program names. An operation of a program with no plan (a bare
  ``jnp.zeros``) is keyed by ``<program>:<operation>``, so a ``fusion``
  of one program and a ``fusion`` of another never share a key.

``jax.profiler.ProfileData`` gives an event's own stats and not those of
its metadata, so this file reads the ``.xplane.pb`` itself: the few
fields of ``XSpace`` it needs, by their protobuf wire numbers (``_PLANE``
... below; tsl/profiler/protobuf/xplane.proto). It imports neither jax
nor a protobuf library.

The program's annotations are the spans of ``obs/trace.py`` (``query``,
``planner.pick``, ``resident.*``...) and its ``timed()`` phases
(``http.q.encode``, ``checkpoint.snapshot``, ``checkpoint.phase``): on
the host plane, the events named in lower case with a dot in the name,
and ``query``. The runtime's own host events (``PjitFunction(..)``,
``ParseArguments``, ``TransferFromDevice``) are not among them. On one
thread they nest; a stretch of time goes to the innermost one open, so
``planner.pick`` gets only what no ``resident.*`` child covers.
"""

from __future__ import annotations

import json
import os
import re
import sys

from benchmarks.lib.xplane import (DEVICE_PLANE, OPS_LINE, TOP, find_xplane,
                                   short_name)

HOST_PLANE = re.compile(r"^/host:CPU$")
PROGRAM_NAME = re.compile(r"^(query|[a-z][a-z0-9_]*(\.[a-z0-9_]+)+)$")


def plan_of(scope: str) -> str | None:
    """``jit(_chunk_fold)/window.chunk_fold/scatter-add`` ->
    ``window.chunk_fold``: the first component of an operation's scope
    that is not a ``jit(..)`` / ``pjit(..)`` wrapper and has more after
    it; None where the scope holds no such name."""
    for part in scope.split("/")[:-1]:
        if not re.match(r"^p?jit\(.*\)$", part):
            return part if PROGRAM_NAME.match(part) else None
    return None


def innermost(events: list[tuple[str, int, int]]):
    """One thread's nested (name, start_ns, duration_ns) events ->
    [(name, start_ns, end_ns)], each stretch under the innermost event
    open in it."""
    out, stack, at = [], [], 0

    def close_until(t):
        nonlocal at
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > at:
                out.append((name, at, end))
                at = end

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > at:
            out.append((stack[-1][0], at, start))
        at = max(at, start)
        stack.append((name, start + dur))
    close_until(float("inf"))
    return out


def covered(stretches, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) inside the union of (start, end)s."""
    total, at = 0, lo
    for s, e in sorted(stretches):
        s, e = max(s, at), min(e, hi)
        if e > s:
            total += e - s
            at = e
    return total


def reduce_planes(planes) -> dict:
    """``planes``: iterable of (plane name, [(line name, [(name,
    start_ns, duration_ns, scope)])]), plain tuples so that a test can
    hand-make one. ``scope`` is the operation's ``tf_op`` on a device
    line, or its program's name where it has no plan ("" where the
    trace says neither), and unused on a host line."""
    ops, by_plan, host = [], {}, []
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            for lname, evs in lines:
                if lname != OPS_LINE:
                    continue
                for name, s, d, scope in evs:
                    if d <= 0:
                        continue
                    ops.append((s, s + d, short_name(name)))
                    key = plan_of(scope) or (
                        f"{scope.split('/')[0] or '(no program)'}:"
                        f"{short_name(name)}")
                    by_plan[key] = by_plan.get(key, 0) + d
        elif HOST_PLANE.match(pname):
            for _lname, evs in lines:
                mine = [(n, s, d) for n, s, d, _ in evs
                        if d > 0 and PROGRAM_NAME.match(n)]
                host += innermost(mine)
    ops.sort()
    gaps, cur_end = [], None
    for s, e, name in ops:
        if cur_end is not None and s > cur_end:
            gaps.append((s - cur_end, cur_end, s, name))
        cur_end = e if cur_end is None else max(cur_end, e)
    out_gaps, gap_ns, named_ns = [], 0, 0
    for length, lo, hi, name in sorted(gaps, reverse=True)[:TOP]:
        inside = [(n, s, e) for n, s, e in host if s < hi and e > lo]
        by_name = {}
        for n in {n for n, _s, _e in inside}:
            by_name[n] = covered(
                [(s, e) for m, s, e in inside if m == n], lo, hi)
        any_ns = covered([(s, e) for _n, s, e in inside], lo, hi)
        gap_ns += length
        named_ns += any_ns
        out_gaps.append({
            "s": length / 1e9, "before": name,
            "host": [[n, ns / 1e9] for n, ns in
                     sorted(by_name.items(), key=lambda kv: -kv[1])],
            "unattributed_s": (length - any_ns) / 1e9})
    return {
        "gaps": out_gaps,
        "gap_s": gap_ns / 1e9,
        "attributed_s": named_ns / 1e9,
        "device_by_plan": [[k, ns / 1e9] for k, ns in sorted(
            by_plan.items(), key=lambda kv: -kv[1])[:2 * TOP]],
    }


# Field numbers of tsl/profiler/protobuf/xplane.proto, as (message,
# field): XSpace.planes; XPlane.name/lines/event_metadata/stat_metadata;
# XLine.name/timestamp_ns/events; XEvent.metadata_id/offset_ps/
# duration_ps; XEventMetadata.name/stats; XStat.metadata_id/uint64_value/
# int64_value/str_value/ref_value; XStatMetadata.name. A map entry is
# (key = 1, value = 2).
_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_UINT, _STAT_INT, _STAT_STR = 1, 3, 4, 5
_STAT_REF = 7
_STAT_META_NAME = 2
_KEY, _VALUE = 1, 2


def _varint(buf, at: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, at
        shift += 7


def _fields(buf):
    """(field number, value) of one serialized message: a varint as an
    int, a length-delimited field as a memoryview; fixed-width fields
    are skipped (this file needs none)."""
    at, end = 0, len(buf)
    while at < end:
        tag, at = _varint(buf, at)
        kind = tag & 7
        if kind == 0:
            val, at = _varint(buf, at)
        elif kind == 2:
            n, at = _varint(buf, at)
            val, at = buf[at:at + n], at + n
        elif kind in (1, 5):
            at += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield tag >> 3, val


def _one(buf, number: int, default=None):
    for num, val in _fields(buf):
        if num == number:
            return val
    return default


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace") if view is not None else ""


def _entries(plane, number: int) -> dict:
    """A ``map<int64, Message>`` field of the plane: id -> message."""
    out = {}
    for num, entry in _fields(plane):
        if num == number:
            out[_one(entry, _KEY, 0)] = _one(entry, _VALUE, b"")
    return out


def _scopes(plane) -> dict[int, tuple[str, str]]:
    """Event metadata id -> (name, scope). The scope is the event's
    ``tf_op`` where that names a plan. The compiler's own operations (a
    copy it inserted) carry none: such a one is put under the plan that
    the rest of its program names, if that is a single one, and else
    its scope is the program's name alone (no plan: keyed by program)."""
    stat_names = {i: _text(_one(m, _STAT_META_NAME))
                  for i, m in _entries(plane, _PLANE_STAT_META).items()}
    found, programs, plans = {}, {}, {}
    for mid, meta in _entries(plane, _PLANE_EVENT_META).items():
        name, tf_op, program = _text(_one(meta, _META_NAME)), "", None
        module = re.match(r"^(.+)\((\d+)\)$", name)
        if module:                      # an event of the XLA Modules line
            programs[int(module.group(2))] = module.group(1)
        for num, stat in _fields(meta):
            if num != _META_STATS:
                continue
            what = stat_names.get(_one(stat, _STAT_META_ID))
            if what == "tf_op":
                ref = _one(stat, _STAT_REF)
                tf_op = (stat_names.get(ref, "") if ref is not None
                         else _text(_one(stat, _STAT_STR)))
            elif what == "program_id":
                program = _one(stat, _STAT_UINT, _one(stat, _STAT_INT))
        found[mid] = (name, tf_op, program)
        if plan_of(tf_op):
            plans.setdefault(program, set()).add(plan_of(tf_op))
    out = {}
    for mid, (name, tf_op, program) in found.items():
        if not plan_of(tf_op):
            tf_op = programs.get(program, "")
            if len(plans.get(program, ())) == 1:
                (plan,) = plans[program]
                tf_op = f"jit({tf_op})/{plan}/"
        out[mid] = (name, tf_op)
    return out


def read_planes(path: str):
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != _PLANES:
            continue
        pname = _text(_one(plane, _PLANE_NAME))
        device = bool(DEVICE_PLANE.match(pname))
        if not device and not HOST_PLANE.match(pname):
            continue
        metas = _scopes(plane)
        lines = []
        for num, line in _fields(plane):
            if num != _PLANE_LINES:
                continue
            lname = _text(_one(line, _LINE_NAME))
            if device and lname != OPS_LINE:
                continue
            base = _one(line, _LINE_TIMESTAMP_NS, 0)
            evs = []
            for num, ev in _fields(line):
                if num != _LINE_EVENTS:
                    continue
                f = dict(_fields(ev))
                name, scope = metas.get(f.get(_EVENT_META_ID), ("", ""))
                if not device and not PROGRAM_NAME.match(name):
                    continue
                evs.append((name,
                            base + f.get(_EVENT_OFFSET_PS, 0) // 1000,
                            f.get(_EVENT_DURATION_PS, 0) // 1000, scope))
            lines.append((lname, evs))
        yield pname, lines


def main(argv: list[str]) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    print(json.dumps(reduce_planes(read_planes(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
