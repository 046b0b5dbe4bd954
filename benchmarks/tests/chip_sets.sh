#!/bin/bash
# How the numbers of PERF.md (PR 24) were taken, from the root of a checkout:
#   chiprun --chips 1 --timeout 3500 -- bash benchmarks/tests/chip_sets.sh <out> "<cells>" "<seeds>" [traced-seed]
# Two sets of runs of every cell on the same seeds (set a, then set b), then one
# traced run of each cell if a seed for it is given. Cells of one config share
# their stores, so they go into one call. Everything a run prints goes to
# $OUT_ROOT/<out>/ (default chiprun_out/, which the chip tool brings back).
O=${OUT_ROOT:-chiprun_out}/$1; CELLS=$2; SEEDS=$3; TRACED=$4
SECONDS_=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
mkdir -p $O
run() { name=$1; shift; python3 -m benchmarks.run "$@" > $O/$name.out 2> $O/$name.err; echo "rc=$? $name" >> $O/rcs.txt; }
for set in a b; do
  for cell in $CELLS; do
    i=0
    for s in $SEEDS; do i=$((i+1)); run $cell.$set$i --workload $cell --seed $s --seconds $SECONDS_ --trace 0; done
  done
done
if [ -n "$TRACED" ]; then
  for cell in $CELLS; do run $cell.t --workload $cell --seed $TRACED --seconds $SECONDS_ --trace 1; done
fi
cat $O/rcs.txt
