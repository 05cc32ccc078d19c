#!/bin/bash
# usage: run_cell.sh <tag> <workload> <seconds> <trace> <seed>...
tag=$1; wl=$2; secs=$3; tr=$4; shift 4
mkdir -p chiprun_out/$tag
for seed in "$@"; do
  python3 benchmark/run.py --workload $wl --seed $seed --seconds $secs --trace $tr > chiprun_out/$tag/s$seed.t$tr.out 2> chiprun_out/$tag/s$seed.t$tr.err
  echo "rc=$? seed=$seed trace=$tr"
  grep -v "INFO" chiprun_out/$tag/s$seed.t$tr.out | tail -8 | cut -c1-1500
  grep -E "Error|error|Traceback" chiprun_out/$tag/s$seed.t$tr.err | head -5
done
