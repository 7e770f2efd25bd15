#!/bin/bash
# usage: [OUT=<dir for logs>] fullsets.sh <cell> <seed>...
# Two sets of 20 s runs of a cell with the same seeds, then one traced run (seed: the first seed + 4000), from the checkout it is started in.
cell=$1; shift
out=${OUT:-chiprun_out/logs}
mkdir -p $out
for set in 1 2; do for s in "$@"; do
  python3 benchmarks/run.py --workload $cell --seed $s --seconds 20 --trace 0 > $out/full_${cell}_set${set}_$s.txt 2>&1; rc=$?
  echo "FULL $cell set$set seed=$s rc=$rc $(tail -n 1 $out/full_${cell}_set${set}_$s.txt)"
  grep "check .*OVER\|setup:\|memory_stats" $out/full_${cell}_set${set}_$s.txt | cut -c1-400
done; done
python3 benchmarks/run.py --workload $cell --seed $(($1 + 4000)) --seconds 20 --trace 1 > $out/full_${cell}_trace.txt 2>&1; echo "TRACE $cell rc=$? $(tail -n 1 $out/full_${cell}_trace.txt)"
