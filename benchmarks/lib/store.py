"""Build a deployment's store from the seed, once, offline.

Run as a child (``python -m benchmarks.lib.store build <config.json>
<seed> <dir>``) with ``JAX_PLATFORMS=cpu``: it opens the program's own storage
engine on ``<dir>/wal``, writes every series through the program's
columnar ingest call (``TSDB.add_batch``, one call a series), shuts
down cleanly (the final checkpoint spills the memtable and snapshots
the sketches) and exits before any daemon starts, so it never competes
for the chip. What it leaves is a store like the one a daemon leaves
behind on SIGTERM; the daemon a run starts replays it and refills its
device window from it.

The socket is left out on purpose: the served write path loads ~48k
points/s (PERF.md, PR 21), which would make set-up twenty minutes a
run. A load cell measures that path; a read cell only needs its result.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from benchmarks.lib import tsbs


def build(cfg: dict, seed: int, out_dir: str) -> dict:
    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.utils.config import Config

    os.makedirs(out_dir)
    wal = os.path.join(out_dir, "wal")
    # As tools/cli.py opens a store for an offline tool: CPU backend, no
    # device window (nothing here queries), everything else default but
    # what the config's ``store`` object says (tsbs.STORE_KEYS).
    conf = Config(wal_path=wal, backend="cpu", auto_create_metrics=True,
                  device_window=False, **cfg.get("store", {}))
    t0 = time.monotonic()
    db = TSDB(MemKVStore(wal_path=wal), conf, start_compaction_thread=False)
    steps = tsbs.loaded_steps(cfg)
    ts = int(cfg["t0"]) + int(cfg["interval_s"]) * np.arange(
        steps, dtype=np.int64)
    tags = tsbs.host_tag_table(cfg, seed)
    points = 0
    try:
        for mi, metric in enumerate(cfg["metrics"]):
            vals = (tsbs.metric_values(cfg, seed, mi, steps) / 100.0).T
            for h, tag_map in enumerate(tags):
                points += db.add_batch(metric, ts, vals[h], tag_map)
    finally:
        db.shutdown()
    return {"config": cfg["name"], "seed": seed, "points": points,
            "series": len(tags) * len(cfg["metrics"]), "steps": steps,
            "build_s": time.monotonic() - t0,
            "bytes": sum(os.path.getsize(os.path.join(out_dir, f))
                         for f in os.listdir(out_dir))}


def count(store_dir: str, start: int, end: int,
          metrics: list[str]) -> dict:
    """Points with start <= ts <= end, from the store's files alone:
    the sstables plus whatever the WAL replays, opened read-only."""
    from opentsdb_tpu.core.const import MAX_TIMESPAN
    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.storage.kv import MemKVStore
    from opentsdb_tpu.utils.config import Config

    wal = os.path.join(store_dir, "wal")
    conf = Config(wal_path=wal, backend="cpu", device_window=False,
                  enable_sketches=False, tenant_accounting=False)
    db = TSDB(MemKVStore(wal_path=wal, read_only=True), conf,
              start_compaction_thread=False)
    points = 0
    try:
        for metric in metrics:
            uid = db.metrics.get_id(metric)
            lo = uid + int(start - start % MAX_TIMESPAN).to_bytes(4, "big")
            hi = uid + int(end - end % MAX_TIMESPAN
                           + MAX_TIMESPAN).to_bytes(4, "big")
            for _key, cols in db.scan_columns(lo, hi):
                ts = cols.timestamps
                points += int(((ts >= start) & (ts <= end)).sum())
    finally:
        db.shutdown()
    return {"points": points}


def main(argv: list[str]) -> int:
    if argv[0] == "build":
        cfg_path, seed, out_dir = argv[1], int(argv[2]), argv[3]
        cfg = tsbs.load_config(cfg_path)
        meta = build(cfg, seed, out_dir)
        with open(os.path.join(out_dir, "STORE.json"), "w") as f:
            json.dump(meta, f)
        print(json.dumps(meta))
    elif argv[0] == "count":
        print(json.dumps(count(argv[1], int(argv[2]), int(argv[3]),
                               argv[4:])))
    else:
        raise SystemExit(f"store: unknown mode {argv[0]!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
