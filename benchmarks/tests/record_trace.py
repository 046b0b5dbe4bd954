"""Record the small trace that ``test_units.py`` reduces.

Run on the chip (``python benchmarks/tests/record_trace.py <out dir>``):
three jitted calls under ``jax.profiler``, the ``.xplane.pb`` copied to
``<out dir>/tiny.xplane.pb`` with what ``xplane.reduce_planes`` makes of
it beside it. Kept to show how ``recorded/`` was made.
"""

import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.lib import xplane  # noqa: E402


def main(out_dir: str) -> int:
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, "trace")
    f = jax.jit(lambda x: jnp.sort(x * 2.0 + 1.0).sum())
    x = jnp.arange(1 << 16, dtype=jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _ in range(3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    src = xplane.find_xplane(trace_dir)
    dst = os.path.join(out_dir, "tiny.xplane.pb")
    shutil.copy(src, dst)
    planes = list(xplane.read_planes(dst))
    for name, lines in planes:
        print(name, [(ln, len(evs)) for ln, evs in lines])
    out = xplane.reduce_planes(planes)
    out["device"] = str(jax.devices()[0].device_kind)
    with open(os.path.join(out_dir, "tiny.expected.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))
    shutil.rmtree(trace_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
