"""The body of a ``/q?...&json`` answer, written from its arrays.

``TSDServer._json_output`` makes one entry a result; ``encode`` turns
the entries into the bytes ``json.dumps(entries).encode()`` would give,
without making a Python object a point or formatting twice what the
executor handed out once: a grid plan's results share one timestamps
array a sub-query and one ``(tags, aggregated)`` a group between every
answer of the plan (query/grid.py, ``_grid_results``).
"""

from __future__ import annotations

import json

import numpy as np

from opentsdb_tpu.obs.registry import METRICS
from opentsdb_tpu.query.grid import KeptTags

# Entries written; of those with a Dps, whether the text of the keys
# was the run's (the same timestamps as an entry before it in this
# answer) or made for it; of those, whether the label's text was found
# on a kept label or made by json.dumps; and the entries that went
# through json.dumps whole.
_M_RESULTS = METRICS.counter("http.q.encode.results")
_M_KEYS_SHARED = METRICS.counter("http.q.encode.keys.shared")
_M_KEYS_FORMATTED = METRICS.counter("http.q.encode.keys.formatted")
_M_LABELS_KEPT = METRICS.counter("http.q.encode.labels.kept")
_M_LABELS_FORMATTED = METRICS.counter("http.q.encode.labels.formatted")
_M_PLAIN = METRICS.counter("http.q.encode.plain")

_KEYS = ("metric", "tags", "aggregateTags", "rollup", "cached", "dps")
_F64 = np.dtype(np.float64)
_I64 = np.dtype(np.int64)


class Dps:
    """An entry's ``dps``: a read-only view of a result's timestamps
    and values. ``items()`` gives what the dict it stands for would,
    ``(str(int(t)), float(v))`` a point; ``encode`` reads the arrays
    and makes no object a point."""

    __slots__ = ("timestamps", "values")

    def __init__(self, timestamps, values) -> None:
        self.timestamps = timestamps
        self.values = values

    def items(self):
        return self.as_dict().items()

    def as_dict(self) -> dict[str, float]:
        return dict(zip(map(str, _int_keys(self.timestamps)),
                        np.asarray(self.values, np.float64).tolist()))


def _int_keys(ts) -> list[int]:
    ts = np.asarray(ts)
    keys = ts.tolist()
    return keys if ts.dtype.kind in "iu" else [int(t) for t in keys]


def _key_text(ts) -> str | None:
    """``"<t0>": %r, "<t1>": %r, ...`` for a timestamps array; None
    where two of them are one key (a dict holds the first only)."""
    keys = _int_keys(ts)
    if len(set(keys)) != len(keys):
        return None
    return ", ".join([f'"{k}": %r' for k in keys])


def _plain(ent: dict) -> str:
    dps = ent.get("dps")
    if type(dps) is Dps:
        ent = {**ent, "dps": dps.as_dict()}
    return json.dumps(ent)


def encode(entries: list[dict]) -> bytes:
    """``json.dumps(entries).encode()``, byte for byte. An entry with
    exactly ``_json_output``'s six keys and a ``Dps`` is written in
    fragments, any other by ``json.dumps``."""
    dumps = json.dumps
    parts: list[str] = []
    add = parts.append
    n_shared = n_formatted = n_kept = n_label = n_plain = 0
    # The run: the timestamps of the entry before and their keys' text;
    # and every int64 timestamps array of this answer by its bytes.
    run_ts = run_keys = None
    by_bytes: dict[bytes, str | None] = {}
    head = (None, None, None)
    head_a = head_b = ""
    for ent in entries:
        dps = ent.get("dps")
        if type(dps) is not Dps or tuple(ent) != _KEYS:
            add(_plain(ent))
            n_plain += 1
            continue
        ts = dps.timestamps
        if ts is run_ts:
            keys = run_keys
            n_shared += 1
        else:
            raw = (ts.tobytes() if type(ts) is np.ndarray
                   and ts.dtype == _I64 else None)
            keys = by_bytes.get(raw)
            if keys is None:
                keys = _key_text(ts)
                n_formatted += 1
                if raw is not None:
                    by_bytes[raw] = keys
            else:
                n_shared += 1
            run_ts, run_keys = ts, keys
        vals = dps.values
        if type(vals) is not np.ndarray or vals.dtype is not _F64:
            vals = np.asarray(vals, np.float64)
        try:
            text = keys % tuple(vals.tolist())
        except TypeError:
            # Keys that are not distinct, or not one a value: as the
            # dict would have it.
            add(_plain(ent))
            n_plain += 1
            continue
        if "n" in text:
            # float.__repr__ is what json.dumps writes of a finite
            # float; of the others it writes these.
            text = text.replace("nan", "NaN").replace("inf", "Infinity")
        this = (ent["metric"], ent["rollup"], ent["cached"])
        if this != head or this[2] is not head[2]:
            head = this
            head_a = '{"metric": ' + dumps(this[0]) + ', "tags": '
            head_b = (', "rollup": ' + dumps(this[1]) + ', "cached": '
                      + dumps(this[2]) + ', "dps": {')
        tags = ent["tags"]
        agg = ent["aggregateTags"]
        kept = type(tags) is KeptTags and tags.aggregated is agg
        label = tags.text if kept else None
        if label is None:
            label = dumps(tags) + ', "aggregateTags": ' + dumps(agg)
            n_label += 1
            if kept:
                tags.text = label
        else:
            n_kept += 1
        add(head_a + label + head_b + text + "}}")
    _M_RESULTS.inc(len(entries))
    _M_KEYS_SHARED.inc(n_shared)
    _M_KEYS_FORMATTED.inc(n_formatted)
    _M_LABELS_KEPT.inc(n_kept)
    _M_LABELS_FORMATTED.inc(n_label)
    _M_PLAIN.inc(n_plain)
    return ("[" + ", ".join(parts) + "]").encode()
