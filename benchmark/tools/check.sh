#!/bin/bash
# A rehearsal of the driver's check on the chip: the committed files alone, unpacked
# twice (two checkouts at two paths), each with a HOME, XDG_CACHE_HOME and TMPDIR of
# its own, no JAX_COMPILATION_CACHE_DIR, BENCH_RUN set; per checkout one compiling run,
# <n> more seeds and a traced run. Make the archive here first:
#   git add -A && git archive $(git write-tree) -o benchmark/out/tree.tar
#   chiprun --chips <c> --timeout 3000 -- bash benchmark/tools/check.sh <cell> <seconds> <n>
# Logs under chiprun_out/check/; a run that fails or retries keeps its daemons' logs too.
cell=$1; secs=$2; n=$3
seeds=(2147483659 2147483777 1999999973 3234567891 987654321 55555 4294967311)
top=$PWD; out=$top/chiprun_out/check; mkdir -p $out
unset JAX_COMPILATION_CACHE_DIR
for set in 1 2; do
  side=$top/benchmark/out/check/side$set
  mkdir -p $side/co $side/home $side/xdg $side/tmp
  tar -xf $top/benchmark/out/tree.tar -C $side/co
  cd $side/co
  for ((i=0;i<=n+1;i++)); do
    s=${seeds[$((i % 7))]}; trace=0; tag=run$i
    if [ $i -eq $((n+1)) ]; then trace=1; tag=trace; s=${seeds[0]}; fi
    log=$out/set${set}_$tag.log
    HOME=$side/home XDG_CACHE_HOME=$side/xdg TMPDIR=$side/tmp BENCH_RUN=set$set.$i \
      python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace $trace > $log 2>&1
    rc=$?
    echo "set $set $tag seed $s rc=$rc $(grep -c '^ATTEMPT' $log) retried"
    grep "^end to end\|^FAILED\|^REFUSED\|WRONG\|^ATTEMPT" $log
    [ $trace -eq 1 ] && tail -n 1 $log | cut -c1-1500
    if [ $rc -ne 0 ] || grep -q '^ATTEMPT' $log; then
      mkdir -p $out/set${set}_$tag.sessions
      cp -r benchmark/out/sessions/. $out/set${set}_$tag.sessions/ 2>/dev/null
      grep -v "^(worker" $log | tail -60
    fi
    pgrep -af "ray_tpu|benchmark/run.py" | grep -v pgrep | sed 's/^/  LEFTOVER: /'
  done
  cd $top
done
