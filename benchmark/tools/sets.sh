#!/bin/bash
# Two sets of runs of one cell on the chip, each run of a set with another seed, the
# same seeds in both sets; results under chiprun_out/<cell>/ (see spread.py).
#   chiprun --chips <n> --timeout 2400 -- bash benchmark/tools/sets.sh <cell> <seconds> <n_seeds> [trace]
# SETS="1" or SETS="2" runs one of the sets.
cell=$1; secs=$2; n=$3
seeds=(2147483659 2147483777 1999999973 1234567891 987654321 55555)
out=chiprun_out/$cell; mkdir -p $out
for set in ${SETS:-1 2}; do
  for ((i=0;i<n;i++)); do
    s=${seeds[$i]}
    t0=$(date +%s.%N)
    python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 0 > $out/set${set}_$s.log 2>&1
    rc=$?; t1=$(date +%s.%N)
    echo "{\"set\": $set, \"seed\": $s, \"rc\": $rc, \"line\": $(tail -n 1 $out/set${set}_$s.log | grep '^{' || echo null)}" >> $out/sets.jsonl
    echo "set $set seed $s rc=$rc"; grep "^end to end\|FAILED\|REFUSED\|WRONG" $out/set${set}_$s.log
    if [ $rc -ne 0 ] && [ $i -eq 0 ]; then   # a cell that does not run: stop, keep the chip time
      grep -v "^(worker" $out/set${set}_$s.log | tail -40; exit 1
    fi
  done
done
if [ "$4" == "trace" ]; then
  python3 benchmark/run.py --workload $cell --seed 2147483659 --seconds $secs --trace 1 > $out/trace.log 2>&1
  echo "trace rc=$?"; tail -n 1 $out/trace.log | cut -c1-3000
fi
