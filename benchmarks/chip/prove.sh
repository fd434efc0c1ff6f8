#!/bin/bash
# Prove one cell on the chip: <sets> sets of <runs> runs, one process a run,
# one after another, the same seeds in every set, sharing the data and
# compile caches the first run leaves; then one traced run; then <more>
# seeds the sets have not had, one run each: the first two traced, the rest
# plain over a third of the window (they are there for `correct` alone).
#
#   chiprun --chips <n> --timeout <s> -- bash benchmarks/chip/prove.sh <cell> <runs> [<sets> [<seconds> [<more>]]]
#
# Each run's last line goes to chiprun_out/<cell>.set<k>.run<i>.json (the
# traced run's to <cell>.trace.json, the further seeds' to
# <cell>.more<i>.json), everything else to chiprun_out/<cell>.log,
# spread.py prints each metric's median and spread at the end, and walls.py
# where the walls' spread lives (between processes, drift, stalls).
set -u
cell=$1 runs=$2 sets=${3:-2} more=${5:-0}
cd "$(dirname "$0")/../.."
seconds=${4:-$(python -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
seeds=(2147483649 104729 3000000019 7 2500000001 1234567891)
further=(1732050807 2236067977 1414213562 3141592653 2718281828 1618033988)
out=chiprun_out
mkdir -p $out
one() {  # <file> <seed> <trace> [<seconds>]
  echo "== $1 seed $2 trace $3 $(date +%T)" >> $out/$cell.log
  python benchmarks/chip/run.py --workload $cell --seed $2 --seconds ${4:-$seconds} --trace $3 \
    > $out/.run.out 2>> $out/$cell.log
  rc=$?
  cat $out/.run.out >> $out/$cell.log
  if [ $rc -eq 0 ]; then tail -n 1 $out/.run.out > $out/$1; else echo "run $1 failed: rc $rc"; fi
  rm -f $out/.run.out
}
for k in $(seq 1 $sets); do
  for i in $(seq 1 $runs); do one $cell.set$k.run$i.json ${seeds[$(( (i - 1) % 6 ))]} 0; done
done
one $cell.trace.json ${seeds[0]} 1
for i in $(seq 1 $more); do
  if [ $i -le 2 ]; then one $cell.more$i.json ${further[$(( i - 1 ))]} 1
  else one $cell.more$i.json ${further[$(( i - 1 ))]} 0 $(( seconds / 3 )); fi
done
python benchmarks/chip/spread.py $out $cell
python benchmarks/chip/walls.py $out/$cell.log
