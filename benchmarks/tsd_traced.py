"""Launcher of the daemon under test, owned by the benchmark.

``python -m benchmarks.tsd_traced --signal-dir D -- tsd <the config's
argv>`` calls the program's own ``opentsdb_tpu.tools.cli.main`` on the
main thread, unchanged. Only the process that holds the chip can ask
JAX about it, so two signal handlers wait here and do nothing until the
harness sends their signal:

- SIGUSR1 writes ``D/memory.json``: ``memory_stats()`` of every local
  device (the result line's ``memory_peak_bytes``). Sent once, after the
  measured window.
- SIGUSR2 starts a helper thread that records a ``jax.profiler`` trace
  into ``D/trace`` until ``D/trace.stop`` appears, then writes
  ``D/trace.json`` with the wall-clock bounds and how long after the
  profile's own start the first of them lies. Sent only in a
  ``--trace 1`` run.

Nothing else is here: what breaks a guarantee to show ``correct`` come
out false lives with the tests (``benchmarks/tests/tsd_control.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time

TRACE_MAX_S = 600.0


def _write(path: str, obj) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def _memory(sig_dir: str) -> None:
    import jax

    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id,
                    "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                    "bytes_in_use": st.get("bytes_in_use"),
                    "bytes_limit": st.get("bytes_limit")})
    _write(os.path.join(sig_dir, "memory.json"), {"devices": out})


def _trace(sig_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    stop = os.path.join(sig_dir, "trace.stop")
    t_call = time.time()
    jax.profiler.start_trace(os.path.join(sig_dir, "trace"),
                             profiler_options=opts)
    t0 = time.time()
    t_end = time.monotonic() + TRACE_MAX_S
    while time.monotonic() < t_end and not os.path.exists(stop):
        time.sleep(0.05)
    t1 = time.time()
    jax.profiler.stop_trace()
    # The profile counts its time from the session's start, inside the
    # call above, and goes on recording for a while after the call
    # below: ``lead_s`` places the bounds on the profile's own clock.
    _write(os.path.join(sig_dir, "trace.json"),
           {"t_start": t0, "t_stop": t1, "lead_s": t0 - t_call,
            "written_s": time.time() - t1})


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--signal-dir", required=True)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    os.makedirs(args.signal_dir, exist_ok=True)
    signal.signal(signal.SIGUSR1,
                  lambda *_: _memory(args.signal_dir))
    signal.signal(signal.SIGUSR2, lambda *_: threading.Thread(
        target=_trace, args=(args.signal_dir,), daemon=True).start())
    from opentsdb_tpu.tools.cli import main as cli_main
    return cli_main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
