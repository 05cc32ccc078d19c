#!/bin/bash
# Two sets of runs with the same seeds, then two traced runs, of one cell
# in one call, as the contract's "bound" rule asks:
#   chiprun [--chips 4] --timeout 3500 -- benchmark/tools/run_sets.sh <tag> <workload> <seconds> <seed>...
# Results land in chiprun_out/<tag>_a, <tag>_b and <tag>_t; read them with
#   python3 benchmark/tools/spread.py chiprun_out/<tag>_a chiprun_out/<tag>_b
tag=$1; wl=$2; secs=$3; shift 3
here=$(dirname "$0")
"$here"/run_cell.sh ${tag}_a $wl $secs 0 "$@" | grep -E "^rc=|^\{" | cut -c1-400
"$here"/run_cell.sh ${tag}_b $wl $secs 0 "$@" | grep -E "^rc=|^\{" | cut -c1-400
"$here"/run_cell.sh ${tag}_t $wl $secs 1 "$1" "$2" | grep -E "^rc=|^\{" | cut -c1-3000
