"""The cell list of a CPU rehearsal: the root ``BENCHMARK.json`` with
each config's file replaced by the tiny one under ``rehearsal/`` that
names it (``rehearsal_of``). Every cell, metric and traffic file stays
the root's own, so there is no second list to keep in step. The cells
of ``benchmarks/pending/`` (entries taken out of the root file, or not
yet in it, until their runs are steady enough for a bound) are added,
so that their generators, readers and checks stay tested: a pending
entry whose name the root already has (``q_mean_ms``, a per-layer
metric of the dash cells) adds its cells to that entry's ``workloads``,
as admitting the cell would; any other is appended.

``python -m benchmarks.tests.rehearsal_cells`` writes it to
``benchmarks/out/rehearsal/BENCHMARK.json`` and prints that path, for
``benchmarks.run --benchmark-json``. With ``--deployment`` the configs
stay the real ones (root file + pending, for a pending cell's runs on
the chip): ``benchmarks/out/pending/BENCHMARK.json``.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(ROOT, "benchmarks", "out")
PATH = os.path.join(OUT, "rehearsal", "BENCHMARK.json")
DEPLOYMENT_PATH = os.path.join(OUT, "pending", "BENCHMARK.json")


def cells(rehearsal: bool = True) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if rehearsal:
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
            have = {e["name"]: e for e in spec[key]}
            for entry in cell[key]:
                if entry["name"] in have:
                    have[entry["name"]]["workloads"] += entry["workloads"]
                else:
                    spec[key].append(entry)
    return spec


def write(rehearsal: bool = True) -> str:
    path = PATH if rehearsal else DEPLOYMENT_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(cells(rehearsal), f)
    return path


if __name__ == "__main__":
    print(write("--deployment" not in sys.argv[1:]))
