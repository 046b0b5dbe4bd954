"""The plain reference of ``server/wire.ingest_batch``: the loop the
daemon ran before a wire chunk became one put (PR 38). The same
operations on the same chunk give the same store, the same error
strings in series order and the same accounts
(tests/test_ingest_chunk.py holds the two equal); it costs by the
series where ``ingest_batch`` costs by the point. No program code
imports it."""

from __future__ import annotations

import numpy as np

from opentsdb_tpu.server import wire


def ingest_batch_reference(tsdb, batch: wire.DecodedBatch,
                           durable: bool = True,
                           tenant: str = "default",
                           ) -> tuple[int, list[str]]:
    """The same chunk, one ``TSDB.add_batch`` (one put, one WAL
    record) a series, in series order, under one covering barrier."""
    n = 0
    errors: list[str] = []
    if len(batch.sid) == 0:
        return 0, errors
    order = np.argsort(batch.sid, kind="stable")
    sid_sorted = batch.sid[order]
    starts = np.concatenate(
        ([0], np.flatnonzero(np.diff(sid_sorted)) + 1, [len(order)]))
    # One covering barrier before this returns, as in ingest_batch.
    try:
        for i in range(len(starts) - 1):
            run = order[starts[i]:starts[i + 1]]
            s = int(sid_sorted[starts[i]])
            metric, tag_map = batch.series[s]
            try:
                n += tsdb.add_batch(
                    metric, batch.timestamps[run], batch.fvalues[run],
                    tag_map, durable=durable,
                    is_float=batch.is_float[run],
                    int_values=batch.ivalues[run], tenant=tenant,
                    sync=False)
            except Exception as e:
                errors.append(wire.series_error(metric, e))
    finally:
        barrier = getattr(tsdb.store, "wal_barrier", None)
        if barrier is not None:
            barrier()
    return n, errors
