"""A traced run of a cell with another span than the harness records:
``python -m benchmarks.tests.run_span SECONDS <benchmarks.run's
arguments>`` records SECONDS from the window's start whatever the
workers' cycles (600 = the whole window, as every traced run before
PR 36). For reading a cell's span against its window on the chip
(PERF.md section 5); never a measured run."""

from __future__ import annotations

import sys

from benchmarks import run


def main(argv: list[str]) -> int:
    run.TRACE_MIN_S, run.TRACE_CYCLES = float(argv[0]), 0
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
