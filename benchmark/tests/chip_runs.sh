#!/bin/bash
# The builder's chip runs of one cell, through the chip tool, one call:
#   chiprun --chips 1 --timeout 3500 -- bash benchmark/tests/chip_runs.sh sets   <cell> <tag>
#   chiprun --chips 1 --timeout 1500 -- bash benchmark/tests/chip_runs.sh traced <cell> <tag>
# "sets": two sets of six untraced runs, the same six seeds in both (the first
# set also reads the fp8 control).  "traced": three traced runs on other seeds.
# Every line a run prints goes to chiprun_out/pr/<tag>.jsonl and .err.
mode=$1; cell=$2; tag=$3
secs=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
mkdir -p chiprun_out/pr
run() { # seed trace extra...
  local t=$SECONDS seed=$1 trace=$2; shift 2
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs --trace $trace "$@" \
     >> chiprun_out/pr/$tag.jsonl 2>> chiprun_out/pr/$tag.err
  echo "rc=$? mode=$mode seed=$seed trace=$trace wall=$((SECONDS-t))" | tee -a chiprun_out/pr/$tag.jsonl >&2
}
if [ "$mode" = sets ]; then
  for set in A B; do
    for seed in 3000000019 3100000037 3200000051 3300000073 3400000091 4100000113; do
      if [ $set = A ]; then run $seed 0 --control fp8; else run $seed 0; fi
    done
  done
else
  for seed in 5000000011 5100000023 5200000047; do run $seed 1 --control fp8; done
fi
grep -E "^correct|wedged|Traceback" chiprun_out/pr/$tag.err | sort | uniq -c | tail
