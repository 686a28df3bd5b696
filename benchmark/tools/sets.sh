#!/bin/sh
# The measurement the bounds are set from: two sets of runs of one cell with
# the same seeds in both, then traced runs; every result line is kept.
#   sh benchmark/tools/sets.sh <cell> <seconds> <out.jsonl> <traced runs> <seed> [<seed> ...]
cell=$1; seconds=$2; out=$3; traced=$4; shift 4
mkdir -p "$(dirname "$out")"
for set in 1 2; do
  for seed in "$@"; do
    line=$(python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 2>>"$out.err" | tail -1)
    echo "{\"set\": $set, \"seed\": $seed, \"trace\": 0, \"line\": $line}" >>"$out"
  done
done
n=0
for seed in "$@"; do
  [ "$n" -ge "$traced" ] && break
  n=$((n + 1))
  line=$(python3 benchmark/run.py --workload "$cell" --seed "$((seed + 1))" --seconds "$seconds" --trace 1 2>>"$out.err" | tail -1)
  echo "{\"set\": 0, \"seed\": $((seed + 1)), \"trace\": 1, \"line\": $line}" >>"$out"
done
