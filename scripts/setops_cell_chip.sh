#!/bin/bash
# Benchmark cell setops_dedup_32m on the chip, this tree beside its parent, in
# ONE call (one compile cache):
#
#   rm -rf _parent && mkdir _parent && git archive <parent commit> | tar -x -C _parent
#   chiprun --chips 1 --timeout 3600 -- bash scripts/setops_cell_chip.sh
#
# The tree's cell traced at seeds 1 2 3 (the first cold), what each
# materialize dispatch said at two seeds (scripts/setops_dispatch.py), then
# the parent's cell traced at the same seeds.  SIDES="tree" or "parent" runs
# one side alone.  Lines and standard error are kept under chiprun_out/pr49/.
T0=$(date +%s)
el() { echo $(( $(date +%s) - T0 )); }
ROOT=$PWD; O=$ROOT/chiprun_out/pr49; mkdir -p $O
CELL=setops_dedup_32m

cell() {   # cell <side> <seed> <trace>: one run; non-zero where it failed or was not correct
  local side=$1 seed=$2 trace=$3 t=$(date +%s) tag=$1.$2.t$3
  python3 benchmark/run.py --workload $CELL --seed $seed --seconds 46 --trace $trace \
      > $O/line.$tag.json 2> $O/stderr.$tag.txt
  local rc=$?
  echo "== $side seed $seed trace $trace: rc $rc, wall $(( $(date +%s) - t )) s (at $(el) s of the call)"
  grep -E "tables from|tables on the device|warm-up|routes:|window:|queries, ms|compiles before|own checks|result pulled|OVER" \
      $O/stderr.$tag.txt | cut -c1-400
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
    print("device_ops", line["breakdown"]["device_ops"][:12])
PY
  [ $rc -eq 0 ] && grep -q '"correct": true' $O/line.$tag.json
}

for side in ${SIDES:-tree parent}; do
  echo "#### $side (at $(el) s)"
  [ $side = parent ] && cd $ROOT/_parent
  for seed in 1 2 3; do
    cell $side $seed 1 || { echo "STOP"; tail -40 $O/stderr.$side.$seed.t1.txt | cut -c1-600; exit 1; }
  done
  if [ $side = tree ]; then
    echo "#### what the dispatches said (at $(el) s)"
    timeout 900 python3 scripts/setops_dispatch.py 1 4800000011 2> $O/dispatch.err || tail -20 $O/dispatch.err
  fi
  cd $ROOT
done
echo "#### the whole call: $(el) s"
