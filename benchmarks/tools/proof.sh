#!/bin/sh
# Two sets of runs of one cell, the same seeds in both, all in one call
# on the chip: sh benchmarks/tools/proof.sh <cell> <seconds> <seed>...
# Writes chiprun_out/proof_<cell>.jsonl and prints the spreads.
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
out=chiprun_out/proof_$cell.jsonl; : > "$out"
for set in 1 2; do
  for seed in "$@"; do
    log=chiprun_out/proof_${cell}_${set}_${seed}.log
    python3 benchmarks/run.py --workload "$cell" --seed "$seed" \
      --seconds "$seconds" --trace 0 > "$log" 2>&1
    rc=$?
    grep "window\]\|FAILED" "$log" | cut -c1-330
    if [ $rc -eq 0 ]; then
      printf '{"set": %s, "seed": %s, "result": %s}\n' "$set" "$seed" \
        "$(tail -n 1 "$log")" >> "$out"
    else
      echo "run failed rc=$rc (set $set seed $seed)"; tail -n 5 "$log"
    fi
  done
done
python3 benchmarks/tools/spread.py "$out"
