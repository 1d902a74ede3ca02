#!/bin/sh
# Run one cell several times in one chip call, each run with another seed
# (development tool; how PERF.md's spreads were measured):
#
#   chiprun [--chips 4] --timeout 3000 -- sh benchmark/tools/measure.sh <out> <cell> <seconds> <trace> <seed>...
#
# Writes chiprun_out/<out>/<cell>.t<trace>.s<seed>.{out,err}; summarize with
#   python3 benchmark/tools/summarize_runs.py chiprun_out/<out> <set size>
out=$1; cell=$2; seconds=$3; trace=$4; shift 4
mkdir -p "chiprun_out/$out"
for seed in "$@"; do
  start=$(date +%s)
  timeout 600 python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" \
    > "chiprun_out/$out/$cell.t$trace.s$seed.out" 2> "chiprun_out/$out/$cell.t$trace.s$seed.err"
  echo "$cell trace=$trace seed=$seed rc=$? wall=$(( $(date +%s) - start ))s"
  tail -n 1 "chiprun_out/$out/$cell.t$trace.s$seed.out" | cut -c1-400
done
