#!/bin/bash
# How the numbers of PERF.md (PR 25) were taken, from the root of a checkout:
#   chiprun --chips 1 --timeout 3500 -- bash benchmarks/tests/chip_tracing.sh <out> "<cells>" <seed> "<runs>"
# <runs> is a list of side:trace, run in that order for each cell: side c is
# this checkout, side p the parent commit unpacked under _archive/parent
# (git archive); trace is 0 or 1. "p:0 c:0 c:1 p:1" gives what tracing costs
# when it is on (a traced run against an untraced one of the same seed, on
# both sides) and what the change costs when it is off. A traced run keeps
# its trace (--keep): the profile, the window's /stats readings and the
# daemon's log come back under $OUT_ROOT/<out>/, with what lib/hostgaps.py and
# lib/xplane.py print for the profile. Both sides share the stores built from
# the seed (the storage code is the same); each has its own compile cache.
O=$(pwd)/${OUT_ROOT:-chiprun_out}/$1; CELLS=$2; SEED=$3; RUNS=$4
SECONDS_=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
mkdir -p $O benchmarks/.cache
if [ -d _archive/parent ] && [ ! -e _archive/parent/benchmarks/.cache ]; then
  ln -s $(pwd)/benchmarks/.cache _archive/parent/benchmarks/.cache
fi
for cell in $CELLS; do
  for r in $RUNS; do
    side=${r%%:*}; trace=${r##*:}; name=$cell.$side$trace
    dir=.; [ $side = p ] && dir=_archive/parent
    keep=; [ $trace = 1 ] && keep=--keep
    (cd $dir && python3 -m benchmarks.run --workload $cell --seed $SEED \
        --seconds $SECONDS_ --trace $trace $keep) > $O/$name.out 2> $O/$name.err
    echo "rc=$? $name" >> $O/rcs.txt
    if [ $trace = 1 ]; then
      w=$dir/benchmarks/out/$cell
      cp $w/stats.json $O/$name.stats.json
      cp $w/tsd.log $O/$name.tsd.log
      find $w/tsd.sig/trace -name '*.xplane.pb' -exec cp {} $O/$name.xplane.pb \;
      JAX_PLATFORMS=cpu python3 -m benchmarks.lib.hostgaps $w/tsd.sig/trace \
          > $O/$name.hostgaps.json 2>> $O/$name.err
      JAX_PLATFORMS=cpu python3 -m benchmarks.lib.xplane $w/tsd.sig/trace \
          > $O/$name.xplane.json 2>> $O/$name.err
      rm -rf $w/store $w/qcache $w/tsd.sig/trace
    fi
  done
done
cat $O/rcs.txt
