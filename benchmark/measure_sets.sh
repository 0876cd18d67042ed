#!/bin/sh
# Two sets of runs of one cell, the same seeds in both, each run a process of
# its own as the driver makes them; the last lines go to chiprun_out/.
#   sh benchmark/measure_sets.sh <cell> <seconds> <seed> [<seed> ...]
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
for set in 1 2; do
  for seed in "$@"; do
    python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 \
      > "chiprun_out/run_${cell}_${set}_${seed}.txt" 2>&1
    echo "set $set seed $seed rc $? $(tail -n 1 "chiprun_out/run_${cell}_${set}_${seed}.txt")" \
      | tee -a "chiprun_out/sets_${cell}.txt"
    grep -E "^(check|serve:|train:)" "chiprun_out/run_${cell}_${set}_${seed}.txt" | grep -v worst
  done
done
