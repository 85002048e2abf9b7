#!/bin/bash
# A builder's helper, never run by the benchmark: several runs of one
# cell in one chip call, each run's whole log under chiprun_out/<dir>/
# and the lines that matter on standard output.
#
#   chiprun --chips 1 --timeout 3000 -- bash benchmark/checks/chip_runs.sh \
#       <dir> <workload> <seconds> <trace> <seed>[c] [<seed>[c] ...]
#
# A seed followed by "c" also computes the fp8 control (--control).
# With FROM=<path> the runs are made from that unpacked checkout (the
# proof that the committed files are enough), with COLD=1 without the
# machine's compile cache, so that the checkout's own, empty one is used.
out=$PWD/chiprun_out/$1; wl=$2; secs=$3; tr=$4; shift 4
mkdir -p "$out"
[ -n "$FROM" ] && cd "$FROM"
[ -n "$COLD" ] && unset JAX_COMPILATION_CACHE_DIR
for seed in "$@"; do
  extra=""
  case $seed in *c) seed=${seed%c}; extra="--control";; esac
  f=$out/${wl}_${seed}_t${tr}_$(ls "$out" | wc -l).log
  timeout 1250 python3 benchmark/run.py --workload "$wl" --seed "$seed" \
    --seconds "$secs" --trace "$tr" $extra > "$f" 2>&1
  echo "rc=$? $wl seed=$seed trace=$tr (from $PWD)"
  grep -E "replica up|persistent compile|warmed|window|TTFT ms|\[correct\]|\[control\]|\[train\]|whole run|^\{|Traceback|Error|failed:| = " "$f" | cut -c1-1500
done
