#!/bin/bash
# How the numbers of PERF.md (PR 36) were taken, from the root of a checkout:
#   chiprun --chips 1 --timeout 3500 -- bash benchmarks/tests/chip_plan.sh <out> <plan>
# <plan> is a text file, a run a line: "<name> <arguments of benchmarks.run>".
# A name that starts with "control=<c>." runs through tests/run_control.py with
# control <c>. The cell list is the root file plus benchmarks/pending/
# (tests/rehearsal_cells.py --deployment), so a pending cell runs at its own
# size; "@PENDING@" in a line stands for that file, and "@MIX@<path>" for a
# copy of it in which the run's --workload reads the mix at <path> (a copy of
# its traffic file with another pace, never committed: the harness finds a
# mix by name, and a name that is a path is that file). A name that starts
# with "span=<s>." runs through tests/run_span.py: a traced span of <s>
# seconds whatever the workers' cycles (600 = the whole window).
# Everything a run
# prints goes to $OUT_ROOT/<out>/ (default chiprun_out/, which the chip tool
# brings back), with the wall seconds of each run in rcs.txt.
O=${OUT_ROOT:-$(pwd)/chiprun_out}/$1; PLAN=$2
mkdir -p $O
PENDING=$(python3 -m benchmarks.tests.rehearsal_cells --deployment)
while read -r name args; do
  [ -z "$name" ] && continue
  case "$args" in
    *@MIX@*)
      mix=$(echo "$args" | sed 's/.*@MIX@\([^ ]*\).*/\1/')
      cell=$(echo "$args" | sed 's/.*--workload \([^ ]*\).*/\1/')
      copy=$(dirname $PENDING)/$name.json
      case $mix in /*) ;; *) mix=$(pwd)/$mix ;; esac
      python3 - "$PENDING" "$copy" "$cell" "${mix%.json}" <<'PY'
import json, sys
spec = json.load(open(sys.argv[1]))
for w in spec["workloads"]:
    if w["name"] == sys.argv[3]:
        w["traffic"] = sys.argv[4]
json.dump(spec, open(sys.argv[2], "w"))
PY
      args=$(echo "$args" | sed "s#@MIX@[^ ]*#$copy#") ;;
  esac
  args=${args//@PENDING@/$PENDING}
  mod=benchmarks.run
  case "$name" in
    control=*) c=${name#control=}; mod="benchmarks.tests.run_control ${c%%.*}" ;;
    span=*) c=${name#span=}; mod="benchmarks.tests.run_span ${c%%.*}" ;;
  esac
  t0=$(date +%s)
  python3 -m $mod $args > $O/$name.out 2> $O/$name.err
  echo "rc=$? $name $(( $(date +%s) - t0 ))s" >> $O/rcs.txt
done < $PLAN
cat $O/rcs.txt
