#!/bin/bash
# Prove one cell on the chip: <sets> sets of <runs> runs, one process a run,
# one after another, the same seeds in every set, sharing the data and
# compile caches the first run leaves; then one traced run.
#
#   chiprun --chips <n> --timeout <s> -- bash benchmarks/chip/prove.sh <cell> <runs> [<sets> [<seconds>]]
#
# Each run's last line goes to chiprun_out/<cell>.set<k>.run<i>.json (the
# traced run's to <cell>.trace.json), everything else to chiprun_out/<cell>.log,
# and spread.py prints each metric's median and spread at the end.
set -u
cell=$1 runs=$2 sets=${3:-2}
cd "$(dirname "$0")/../.."
seconds=${4:-$(python -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
seeds=(2147483649 104729 3000000019 7 2500000001 1234567891)
out=chiprun_out
mkdir -p $out
one() {  # <file> <seed> <trace>
  echo "== $1 seed $2 trace $3 $(date +%T)" >> $out/$cell.log
  python benchmarks/chip/run.py --workload $cell --seed $2 --seconds $seconds --trace $3 \
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
python benchmarks/chip/spread.py $out $cell
