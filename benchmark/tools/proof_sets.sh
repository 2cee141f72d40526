#!/bin/bash
# Runs of one cell in one chip call, one a seed, then one traced run:
#
#   chiprun --timeout 2400 -- bash benchmark/tools/proof_sets.sh <cell> <seed>...
#
# Prints each run's summary line, its set-up by phase and its result line.
# How PR 23's proof sets were made (PERF.md); not part of a benchmark run.
W=$1; shift
KEEP='^bench open_loop\|^bench backlog\|^bench train\|^bench setup\|^bench program\|^{\|Error\|benchmark:'
for s in "$@"; do
  echo "SEED $s"
  python3 benchmark/run.py --workload "$W" --seed "$s" --seconds 51 --trace 0 2>&1 | grep "$KEEP" | cut -c1-1200
done
echo TRACED
python3 benchmark/run.py --workload "$W" --seed 2147483199 --seconds 51 --trace 1 2>&1 | grep "$KEEP" | cut -c1-3500
