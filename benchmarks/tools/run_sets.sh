#!/bin/bash
# Two sets of runs of one cell (the same seeds in both) and some traced
# runs, all in one call on the chip; every result line lands in
# chiprun_out/sets_<cell>.jsonl with its set and seed, every run's stderr
# in chiprun_out/sets_<cell>_<set>_<seed>.err.
#   chiprun --chips 1 --timeout 3500 -- bash benchmarks/tools/run_sets.sh <cell> [<cell> ...]
# SEEDS / TRACED (space-separated) replace the default seeds; OUT_DIR the
# directory (for a run from a checkout inside the repo's copy).
OUT_DIR=${OUT_DIR:-chiprun_out}
SEEDS=${SEEDS:-"2147483659 2147483693 3000000019 1999999973 2147480009 2147400013"}
TRACED=${TRACED:-"2147483777 3000000077 1999999777"}
mkdir -p $OUT_DIR
SECONDS_RUN=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
run() {  # cell set seed trace
  python3 benchmarks/run.py --workload $1 --seed $3 --seconds $SECONDS_RUN --trace $4 > $OUT_DIR/last.out 2> $OUT_DIR/sets_$1_$2_$3.err
  echo "{\"set\": \"$2\", \"seed\": $3, \"rc\": $?, \"line\": $(tail -n 1 $OUT_DIR/last.out)}" >> $OUT_DIR/sets_$1.jsonl
}
for W in "$@"; do
  : > $OUT_DIR/sets_$W.jsonl
  for SET in A B; do
    for SEED in $SEEDS; do run $W $SET $SEED 0; done
  done
  for SEED in $TRACED; do run $W T $SEED 1; done
  tail -c 1500 $OUT_DIR/sets_$W.jsonl
done
