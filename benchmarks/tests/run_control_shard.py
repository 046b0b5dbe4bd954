"""A run of the four-chip cell with its daemon started through
``tsd_control_shard.py``: ``python -m benchmarks.tests.run_control_shard
NAME <benchmarks.run's arguments>``. For ``test_mesh4_cell.py``, and for
reading the control on the chips at the cell's own size; never a
measured run."""

from __future__ import annotations

import sys

from benchmarks import run
from benchmarks.lib import daemon


def main(argv: list[str]) -> int:
    daemon.LAUNCHER = ["-m", "benchmarks.tests.tsd_control_shard",
                       "--control", argv[0]]
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
