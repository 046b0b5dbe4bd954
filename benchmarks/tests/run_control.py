"""A run of a cell with its daemon started through ``tsd_control.py``:
``python -m benchmarks.tests.run_control NAME <benchmarks.run's
arguments>``. For the tests here, and for reading a control on the chip
at the cell's own size (PERF.md section 2); never a measured run."""

from __future__ import annotations

import sys

from benchmarks import run
from benchmarks.lib import daemon


def main(argv: list[str]) -> int:
    daemon.LAUNCHER = ["-m", "benchmarks.tests.tsd_control", "--control",
                       argv[0]]
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
