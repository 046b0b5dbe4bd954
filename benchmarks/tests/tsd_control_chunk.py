"""The live cell's three controls, planted on the path it times: the
measured launcher with one guarantee broken where a wire chunk goes in
as ONE put (``TSDB.add_chunk`` -> one ``put_many_columnar`` -> one
``DeviceWindow.append_many`` a metric). Test-only, like
``tsd_control.py``, whose controls of the same names patch the
one-series entry points (``DeviceWindow.append``, 2,500 small WAL
records a chunk) that a served put no longer goes through.

``python -m benchmarks.tests.tsd_control_chunk --control NAME <what
benchmarks.tsd_traced takes>``; ``run_control_chunk.py`` beside it is
the only way in.

- ``drop_staged_steps``: a chunk's points are acknowledged, written to
  the WAL and stored, and every second step of them never reaches the
  device window's staged batch, so the resident plan answers without
  them while the files recount whole.
- ``late_staged_steps``: the same for a while only: a chunk reaches the
  staged batch ``LATE_S`` seconds (one step; ``late_staged_steps:2`` for
  2) after it was written, in order.
- ``wal_unflushed``: the acknowledgement before the WAL's flush. A
  chunk's record (180 KB at 2,500 points) is over Python's 8 KB file
  buffer, which writes such a record through at the append, flush or no
  flush; so the control holds the WAL in a buffer that takes the record,
  as a small record is held by the default one, and skips the flush.
"""

from __future__ import annotations

import sys

from benchmarks import tsd_traced
from benchmarks.tests.tsd_control import LATE_S

WAL_BUFFER = 1 << 20


def of_a_put(series_of_point, timestamps) -> bool:
    """A put's chunk is many series, or a few points of one; the boot's
    refill appends whole row-hours of one series and is left alone."""
    return series_of_point is not None or len(timestamps) < 64


def apply_control(name: str) -> None:
    name, _, arg = name.partition(":")
    if name == "drop_staged_steps":
        from opentsdb_tpu.storage.devstore import DeviceWindow
        append_many = DeviceWindow.append_many

        def sparse(self, metric_uid, series_keys, series_of_point,
                   timestamps, values):
            if of_a_put(series_of_point, timestamps):
                keep = timestamps % 20 != 0
                timestamps, values = timestamps[keep], values[keep]
                if series_of_point is not None:
                    series_of_point = series_of_point[keep]
            return append_many(self, metric_uid, series_keys,
                               series_of_point, timestamps, values)
        DeviceWindow.append_many = sparse
    elif name == "late_staged_steps":
        import queue
        import threading
        import time

        from opentsdb_tpu.storage.devstore import DeviceWindow
        append_many = DeviceWindow.append_many
        waiting: queue.Queue = queue.Queue()
        late_s = float(arg or LATE_S)

        def late(self, metric_uid, series_keys, series_of_point,
                 timestamps, values):
            if not of_a_put(series_of_point, timestamps):
                return append_many(self, metric_uid, series_keys,
                                   series_of_point, timestamps, values)
            waiting.put((time.monotonic() + late_s, self, metric_uid,
                         list(series_keys),
                         None if series_of_point is None
                         else series_of_point.copy(),
                         timestamps.copy(), values.copy()))

        def apply():
            while True:
                due, *call = waiting.get()
                time.sleep(max(due - time.monotonic(), 0.0))
                append_many(*call)
        DeviceWindow.append_many = late
        threading.Thread(target=apply, daemon=True).start()
    elif name == "wal_unflushed":
        from opentsdb_tpu.storage.kv import MemKVStore
        append = MemKVStore._wal_append_batch_columnar

        def held(self, *a, **k):
            # Under the store's lock, like the append: the WAL the store
            # opened (at boot, after a rotation) gives way to one on the
            # same file with the larger buffer.
            if (self._wal is not None
                    and getattr(self, "_held_wal", None) is not self._wal):
                self._wal.flush()
                self._wal = self._held_wal = open(
                    self._wal.name, "ab", buffering=WAL_BUFFER)
            return append(self, *a, **k)
        MemKVStore._wal_append_batch_columnar = held
        MemKVStore._wal_flush = lambda self: None
    else:
        raise SystemExit(f"tsd_control_chunk: unknown control {name!r}")


def main(argv: list[str]) -> int:
    if argv[:1] != ["--control"] or len(argv) < 2:
        raise SystemExit(
            "usage: tsd_control_chunk --control NAME <launcher args>")
    apply_control(argv[1])
    return tsd_traced.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
