#!/bin/bash
# How the bounds' spreads were measured (PERF.md §2, §6): for one cell two
# sets of 6 runs with the same seeds in both, then 3 traced runs on further
# seeds — all in one call, from the repo's root, on the machine with the
# chip:
#     bash benchmark/tools/measure_sets.sh <cell> <out_dir> [seconds] [runs]
cell=$1; out=$2; secs=${3:-40}; runs=${4:-6}; mkdir -p "$out"
seeds=$(echo 1000000007 2147483659 2200000033 123456791 1618033989 2019201817 \
  | cut -d' ' -f1-"$runs")
for k in 1 2; do i=0; for s in $seeds; do i=$((i+1))
  python3 benchmark/run.py --workload "$cell" --seed "$s" --seconds "$secs" \
    --trace 0 > "$out/set${k}_$i.out" 2> "$out/set${k}_$i.err"
  echo "set$k $i seed $s rc=$? $(tail -n 1 "$out/set${k}_$i.out" | cut -c1-330)"
done; done
i=0; for s in 888000888 999000999 2987654321; do i=$((i+1))
  python3 benchmark/run.py --workload "$cell" --seed "$s" --seconds "$secs" \
    --trace 1 > "$out/trace_$i.out" 2> "$out/trace_$i.err"
  echo "trace $i seed $s rc=$? $(tail -n 1 "$out/trace_$i.out" | cut -c1-600)"
done
grep -h "setup_s=" "$out"/set*.err | cut -c1-200
