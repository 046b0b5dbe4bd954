"""Where in a traced span the device was busy: ``python -m
benchmarks.tests.span_parts <trace dir> <bucket seconds>`` (with
``JAX_PLATFORMS=cpu``, once the daemon is gone, on the trace a ``--keep``
run leaves under ``benchmarks/out/<cell>/tsd.sig/trace``). Prints one
JSON object: the busy seconds of each bucket counted from the first
operation, and the ten longest gaps with the second they began at. Kept
to show how PERF.md section 5 told a span's idle share from the whole
window's (PR 36); no run calls it."""

from __future__ import annotations

import json
import sys

from benchmarks.lib import xplane


def parts(planes, bucket_s: float) -> dict:
    iv = sorted(
        (s, s + d, xplane.short_name(n))
        for pname, lines in planes if xplane.DEVICE_PLANE.match(pname)
        for ln, evs in lines if ln == xplane.OPS_LINE
        for n, s, d in evs if d > 0)
    busy, _gaps = xplane.union(iv)
    t0, width = busy[0][0], int(bucket_s * 1e9)
    out = [0] * ((busy[-1][1] - t0) // width + 1)
    for s, e in busy:
        while s < e:
            k = (s - t0) // width
            cut = min(e, t0 + (k + 1) * width)
            out[k] += cut - s
            s = cut
    gaps = sorted(((b[0] - a[1], a[1] - t0) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:xplane.TOP]
    return {"bucket_s": bucket_s, "busy_s": [ns / 1e9 for ns in out],
            "longest_gaps": [[ns / 1e9, at / 1e9] for ns, at in gaps]}


if __name__ == "__main__":
    print(json.dumps(parts(xplane.read_planes(
        xplane.find_xplane(sys.argv[1])), float(sys.argv[2]))))
