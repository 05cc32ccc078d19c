#!/bin/sh
# Reproducible non-test source LoC count (advisor r2: state the exact
# command). Counts Python/C++ under the package + native + CLIs + drivers.
cd "$(dirname "$0")/.."
find deepspeed_tpu csrc bin examples -name '*.py' -o -name '*.cpp' -o -name 'dstpu*' \
  | grep -v __pycache__ | sort | xargs wc -l | tail -1
wc -l chip_smoke.py __graft_entry__.py benchmark/run.py | tail -1
