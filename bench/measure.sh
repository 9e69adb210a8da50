#!/bin/bash
# Two sets of runs of one cell with the same seeds in both, then traced
# runs, each run a process of its own; one JSON result line per run in
# <dir>/measure_<cell>.jsonl (the spread of these sets the bounds).
#   bash bench/measure.sh <cell> <seconds> "<seeds>" "<traced seeds>" [<dir>]
cell=$1; secs=$2; seeds=$3; traced=$4; dir=${5:-.bench_out}
out=$dir/measure_$cell.jsonl
mkdir -p $dir
for set in 1 2; do
  for s in $seeds; do
    line=$(timeout 1300 python3 bench/run.py --workload $cell --seed $s --seconds $secs --trace 0 2>$dir/err_$cell.txt | tail -n 1)
    echo "{\"set\": $set, \"seed\": $s, \"trace\": 0, \"result\": ${line:-null}}" >> $out
    tail -n 4 $dir/err_$cell.txt
  done
done
for s in $traced; do
  line=$(timeout 1300 python3 bench/run.py --workload $cell --seed $s --seconds $secs --trace 1 2>$dir/err_$cell.txt | tail -n 1)
  echo "{\"set\": 0, \"seed\": $s, \"trace\": 1, \"result\": ${line:-null}}" >> $out
  tail -n 4 $dir/err_$cell.txt
done
