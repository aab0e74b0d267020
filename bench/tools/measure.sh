#!/bin/sh
# The measurements behind a cell's bounds and limits, on the machine that
# holds the chip (each numbered seed is used once per role):
#   readings: 6 seeds in one process, the fp8 control on the first 3;
#   sets A and B: the same 6 seeds, one process per run;
#   traced: 3 seeds with --trace 1.
#   sh bench/tools/measure.sh <cell> <seed base> <seconds> [out dir]
cell=$1; b=$2; secs=$3; out=${4:-bench_out/$cell}
mkdir -p "$out"
s() { echo $((b + $1)); }
python3 bench/tools/readings.py --workload "$cell" --seconds "$secs" \
  --seeds "$(s 1),$(s 2),$(s 3),$(s 4),$(s 5),$(s 6)" \
  --control "$(s 1),$(s 2),$(s 3)" > "$out/readings.out" 2> "$out/readings.err"
echo "== readings rc=$?"; cut -c1-900 "$out/readings.out"
set6="$cell:$(s 11) $cell:$(s 12) $cell:$(s 13) $cell:$(s 14) $cell:$(s 15) $cell:$(s 16)"
sh bench/tools/cells.sh "$out/A" "$secs" 0 $set6
sh bench/tools/cells.sh "$out/B" "$secs" 0 $set6
sh bench/tools/cells.sh "$out/T" "$secs" 1 "$cell:$(s 21)" "$cell:$(s 22)" "$cell:$(s 23)"
