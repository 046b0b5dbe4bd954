"""The cell list of a CPU rehearsal: the root ``BENCHMARK.json`` with
each config's file replaced by the tiny one under ``rehearsal/`` that
names it (``rehearsal_of``). Every cell, metric and traffic file stays
the root's own, so there is no second list to keep in step. The cells
of ``benchmarks/pending/`` (entries taken out of the root file until
their runs are steady enough for a bound) are added, so that their
generators, readers and checks stay tested.

``python -m benchmarks.tests.rehearsal_cells`` writes it to
``benchmarks/out/rehearsal/BENCHMARK.json`` and prints that path, for
``benchmarks.run --benchmark-json``.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PATH = os.path.join(ROOT, "benchmarks", "out", "rehearsal", "BENCHMARK.json")


def cells() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    small = {}
    for name in sorted(os.listdir(os.path.join(HERE, "rehearsal"))):
        with open(os.path.join(HERE, "rehearsal", name)) as f:
            small[json.load(f)["rehearsal_of"]] = os.path.join(
                "benchmarks", "tests", "rehearsal", name)
    for conf in spec["configs"]:
        conf["file"] = small[conf["name"]]
    pending = os.path.join(ROOT, "benchmarks", "pending")
    for name in sorted(os.listdir(pending)):
        with open(os.path.join(pending, name)) as f:
            cell = json.load(f)
        for key in ("workloads", "end_to_end", "per_layer"):
            spec[key] += cell[key]
    return spec


def write() -> str:
    os.makedirs(os.path.dirname(PATH), exist_ok=True)
    with open(PATH, "w") as f:
        json.dump(cells(), f)
    return PATH


if __name__ == "__main__":
    print(write())
