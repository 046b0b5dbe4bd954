"""The measured launcher with one guarantee broken first: test-only.

``python -m benchmarks.tests.tsd_control --control NAME <what
benchmarks.tsd_traced takes>`` patches the program in the daemon's own
process and then hands over to ``benchmarks.tsd_traced.main``. It
exists to show ``correct`` come out false; no measured run starts it
(``run_control.py`` beside it is the only way in).

- ``wire_bf16``: the program's one lower-precision path
  (``Config.wire_bf16``, the [G, B] answer cast to bfloat16 on the
  device), which has no CLI flag, switched on in every ``Config``.
- ``wal_unflushed``: the acknowledgement before the WAL's flush.
- ``answer_off_4e-3``: the timed path broken underneath, an answer
  altered where it is produced (every value 0.4% up).
- ``drop_last_point``: the same for writes, a point acknowledged and
  not stored (each batch loses its last).
- ``drop_staged_steps``: a live deployment's guarantee broken: a point
  is acknowledged, written to the WAL and stored, and never reaches
  the device window's staged batch (every second step of what the
  collectors send), so the resident plan answers without it while the
  files recount whole.
- ``late_staged_steps``: the same guarantee broken for a while only: a
  put's points reach the staged batch ``LATE_S`` seconds (one step of
  the deployment; ``late_staged_steps:2`` for 2) after they were
  written, in order. Everything comes back whole in the end; only a
  request written soon after the edge moved lacks points of the step.
"""

from __future__ import annotations

import sys

from benchmarks import tsd_traced

LATE_S = 10.0


def apply_control(name: str) -> None:
    name, _, arg = name.partition(":")
    if name == "wire_bf16":
        from opentsdb_tpu.utils.config import Config
        init = Config.__init__

        def patched(self, *a, **k):
            init(self, *a, **k)
            self.wire_bf16 = True
        Config.__init__ = patched
    elif name == "wal_unflushed":
        from opentsdb_tpu.storage.kv import MemKVStore
        MemKVStore._wal_flush = lambda self: None
    elif name == "answer_off_4e-3":
        from opentsdb_tpu.server.tsd import TSDServer
        render = TSDServer._json_output

        def off(self, *a, **k):
            out = render(self, *a, **k)
            for ent in out:
                ent["dps"] = {t: v * 1.004 for t, v in ent["dps"].items()}
            return out
        TSDServer._json_output = off
    elif name == "drop_last_point":
        from opentsdb_tpu.core.tsdb import TSDB
        add = TSDB.add_batch

        def short(self, metric, timestamps, values, *a, **k):
            for key in ("is_float", "int_values"):
                if k.get(key) is not None:
                    k[key] = k[key][:-1]
            return add(self, metric, timestamps[:-1], values[:-1], *a, **k)
        TSDB.add_batch = short
    elif name == "drop_staged_steps":
        from opentsdb_tpu.storage.devstore import DeviceWindow
        append = DeviceWindow.append

        def sparse(self, metric_uid, series_key, timestamps, values):
            # A put's batch is a few points a series; the boot's refill
            # appends whole row-hours and is left alone.
            if len(timestamps) < 64:
                keep = timestamps % 20 != 0
                timestamps, values = timestamps[keep], values[keep]
            return append(self, metric_uid, series_key, timestamps, values)
        DeviceWindow.append = sparse
    elif name == "late_staged_steps":
        import queue
        import threading
        import time

        from opentsdb_tpu.storage.devstore import DeviceWindow
        append = DeviceWindow.append
        waiting: queue.Queue = queue.Queue()
        late_s = float(arg or LATE_S)

        def late(self, metric_uid, series_key, timestamps, values):
            if len(timestamps) >= 64:        # the boot's refill
                return append(self, metric_uid, series_key, timestamps,
                              values)
            waiting.put((time.monotonic() + late_s, self, metric_uid,
                         series_key, timestamps.copy(), values.copy()))

        def apply():
            while True:
                due, *call = waiting.get()
                time.sleep(max(due - time.monotonic(), 0.0))
                append(*call)
        DeviceWindow.append = late
        threading.Thread(target=apply, daemon=True).start()
    else:
        raise SystemExit(f"tsd_control: unknown control {name!r}")


def main(argv: list[str]) -> int:
    if argv[:1] != ["--control"] or len(argv) < 2:
        raise SystemExit("usage: tsd_control --control NAME <launcher args>")
    apply_control(argv[1])
    return tsd_traced.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
