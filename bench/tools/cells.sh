#!/bin/sh
# Run cells on the machine that holds the chip, each run its own process
# as a benchmark check runs them, and keep each run's output in <out dir>.
#   sh bench/tools/cells.sh <out dir> <seconds> <trace> <cell>:<seed> ...
out=$1; secs=$2; trace=$3; shift 3
mkdir -p "$out"
for cs in "$@"; do
  cell=${cs%%:*}; seed=${cs#*:}
  t=$(date +%s)
  python3 bench/run.py --workload "$cell" --seed "$seed" --seconds "$secs" \
    --trace "$trace" > "$out/${cell}_${seed}_t${trace}.out" \
    2> "$out/${cell}_${seed}_t${trace}.err"
  rc=$?
  echo "== $cell seed $seed trace $trace rc=$rc wall $(( $(date +%s) - t )) s"
  grep -E "^set-up|^reference|^check|^the served|^itl_p99|lateness|Error|error" "$out/${cell}_${seed}_t${trace}.err" | tail -8
  tail -1 "$out/${cell}_${seed}_t${trace}.out" | cut -c1-1500
done
