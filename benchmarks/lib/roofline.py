"""What an answer needs from memory, and the share of the peak that is.

The bytes are those the *question* needs, not those a kernel chooses
to read: 4 bytes (one float32 value) for every point of every series
the filter matches inside the window, summed over the requests. Any
exact kernel reads at least that much, so the share cannot pass 100%,
and a kernel that reads all 4,000 hosts for an 8-host question shows
as the small share it is.
"""

from __future__ import annotations

import json
import os

BYTES_PER_POINT = 4


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def needed_bytes(series_steps: int) -> int:
    """``series_steps``: sum over requests of (series matched) x (steps
    inside the window)."""
    return BYTES_PER_POINT * int(series_steps)


def hbm_share_pct(series_steps: int, busy_s: float, device_kind: str):
    """Needed bytes over what the memory could have moved while the
    device was busy, in percent; None where the device was never busy."""
    if busy_s <= 0:
        return None
    peak = load_peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * needed_bytes(series_steps) / (peak * busy_s)
