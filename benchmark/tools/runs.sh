#!/bin/sh
# Run one cell several times in one call, one process after another, and keep
# each run's result line and the end of its standard error under chiprun_out/.
#   sh benchmark/tools/runs.sh <tag> <workload> <seconds> <trace 0|1> <seed>...
tag=$1; workload=$2; seconds=$3; trace=$4; shift 4
mkdir -p chiprun_out
for seed in "$@"; do
  out=chiprun_out/$tag.$seed
  t0=$(date +%s)
  python3 benchmark/run.py --workload "$workload" --seed "$seed" \
    --seconds "$seconds" --trace "$trace" > "$out.out" 2> "$out.err"
  rc=$?
  echo "RUN $tag seed=$seed rc=$rc wall=$(( $(date +%s) - t0 ))s"
  grep -E '^(compared|correct|counters|check took|trace:|kernel_roofline|percentile|memory_stats|grad norms|delta norms)' "$out.err" | tail -n 24
  tail -n 1 "$out.out" | tee -a "chiprun_out/$tag.results.jsonl"
done
