"""The four-chip cell's control, planted where the shards' grids are
gathered: the measured launcher with one shard's answer misplaced.
Test-only, like ``tsd_control.py``.

``python -m benchmarks.tests.tsd_control_shard --control NAME <what
benchmarks.tsd_traced takes>``; ``run_control_shard.py`` beside it is
the only way in.

- ``shard_rows_shifted``: the [series, bucket] values that the shard
  on the last device hands to the gather arrive one row late (row ``i``
  holds series ``i - 1``'s buckets; the masks stay, so every answer
  keeps its shape), as a gather that sliced or concatenated a shard's
  rows at the wrong offset would leave them. Every series of that shard
  then answers with its neighbour's values; the three other shards'
  answers are whole.
"""

from __future__ import annotations

import sys

from benchmarks import tsd_traced


def apply_control(name: str) -> None:
    if name == "shard_rows_shifted":
        import jax
        import jax.numpy as jnp

        from opentsdb_tpu.ops import kernels
        stage = kernels.window_series_stage_chunks

        def shifted(chunks, *a, **k):
            grids = stage(chunks, *a, **k)
            # Asked inside the call: the daemon has its backend by now.
            last = max(d.id for d in jax.local_devices()[:4])
            on = {d.id for c in chunks[:1] for d in c[0].devices()}
            if on != {last}:
                return grids
            return tuple(jnp.roll(g, 1, axis=0)
                         if jnp.issubdtype(g.dtype, jnp.floating) else g
                         for g in grids)
        kernels.window_series_stage_chunks = shifted
    else:
        raise SystemExit(f"tsd_control_shard: unknown control {name!r}")


def main(argv: list[str]) -> int:
    if argv[:1] != ["--control"] or len(argv) < 2:
        raise SystemExit(
            "usage: tsd_control_shard --control NAME <launcher args>")
    apply_control(argv[1])
    return tsd_traced.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
