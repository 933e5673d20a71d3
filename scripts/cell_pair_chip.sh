#!/bin/bash
# Benchmark cells on the chip, this tree beside its parent, in ONE call
# (one machine, one compile cache):
#
#   rm -rf _parent && mkdir _parent && git archive <parent commit> | tar -x -C _parent
#   chiprun --chips 1 --timeout 3600 -- bash scripts/cell_pair_chip.sh <tag> <cell> [<cell> ...]
#
# Each cell runs ORDER's sides (default "parent tree tree parent"), the k-th
# run at the k-th of SEEDS (default: the two sides of a pair share a seed, no
# two pairs do), every run with TRACE (default 1: the end-to-end metrics and
# the per-layer ones from one run).  Lines and standard error are kept under
# chiprun_out/<tag>/.  Both sides keep their compiled programs in one
# directory of the call's machine, so a program whose text did not change
# compiles once.  A run is not started past DEADLINE seconds (default 3000:
# a call lasts 3600 at most, a cold run ~6 minutes) after CALL_T0, which a
# command that runs this script more than once exports (`date +%s`).
T0=$(date +%s)
el() { echo $(( $(date +%s) - T0 )); }
ROOT=$PWD; TAG=$1; shift
O=$ROOT/chiprun_out/$TAG; mkdir -p $O
ORDER=(${ORDER:-parent tree tree parent})
SEEDS=(${SEEDS:-5000000011 5000000011 5000000029 5000000029})
TRACE=${TRACE:-1}
DEADLINE=${DEADLINE:-3000}
up() { echo $(( $(date +%s) - ${CALL_T0:-$T0} )); }
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache}

run() {   # run <cell> <side> <seed>: one run; non-zero where it failed or was not correct
  local cell=$1 side=$2 seed=$3 t=$(date +%s) tag=$1.$2.$3.t$TRACE
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 46 --trace $TRACE \
      > $O/line.$tag.json 2> $O/stderr.$tag.txt
  local rc=$?
  echo "== $cell $side seed $seed trace $TRACE: rc $rc, wall $(( $(date +%s) - t )) s (at $(el) s of the call)"
  grep -E "routes:|compiles before|OVER" $O/stderr.$tag.txt | cut -c1-300
  python3 - $O/line.$tag.json <<'PY'
import json, sys
try:
    line = json.loads(open(sys.argv[1]).read().strip().splitlines()[-1])
except Exception as e:
    print("no result line:", e); sys.exit(0)
print("correct", line["correct"], "attempted", line["attempted"], "device", line["device"])
print({k: v["value"] for k, v in line["metrics"].items()})
print("over:", {k: v for k, v in line["compared"].items() if v["value"] > v["limit"]})
if "breakdown" in line:
    print("device_ops", line["breakdown"]["device_ops"][:10])
PY
  [ $rc -eq 0 ] && grep -q '"correct": true' $O/line.$tag.json
}

for cell in "$@"; do
  for k in "${!ORDER[@]}"; do
    side=${ORDER[$k]}
    [ $(up) -gt $DEADLINE ] && { echo "#### $cell $side: skipped, $(up) s into the call"; continue; }
    echo "#### $cell $side (at $(el) s)"
    [ $side = parent ] && cd $ROOT/_parent
    run $cell $side ${SEEDS[$k]} || {
      echo "STOP"; tail -40 $O/stderr.$cell.$side.${SEEDS[$k]}.t$TRACE.txt | cut -c1-600; exit 1; }
    cd $ROOT
  done
done
echo "#### the whole call: $(el) s"
