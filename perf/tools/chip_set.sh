#!/bin/bash
# Runs of one cell in one chip call, each in a process of its own:
#   perf/tools/chip_set.sh <out dir under chiprun_out> <workload> <seconds> <trace> <seed> [<seed> ...]
# Prints each run's result line; whole logs go to the out dir. From a
# tree unpacked inside the repository (git archive into chip_trees/),
# CHIPRUN_OUT=/root/repo/chiprun_out sends them where the chip tool
# brings them back from.
out=${CHIPRUN_OUT:-chiprun_out}/$1; cell=$2; seconds=$3; trace=$4; shift 4
mkdir -p "$out"
for seed in "$@"; do
  log="$out/${cell}_t${trace}_${seed}_$(date +%s).log"
  python3 -m perf.run --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" > "$log" 2> "${log%.log}.err"
  echo "run $cell seed $seed trace $trace rc=$? $(tail -n 1 "$log" | cut -c1-1800)"
done
