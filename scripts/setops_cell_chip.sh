#!/bin/bash
# Everything benchmark cell setops_dedup_32m still needs from the chip, in ONE
# call (one compile cache) - PR 48 got no machine and left this unrun:
#
#   rm -rf _parent && mkdir _parent && git archive <parent commit> | tar -x -C _parent
#   cp BENCHMARK.json _parent/ && cp -r benchmark/. _parent/benchmark/   # this tree's files over the parent
#   chiprun --chips 1 --timeout 3600 -- bash scripts/setops_cell_chip.sh
#
# The cell cold then warm at seeds 1 2 3, one traced run, the parent under
# this tree's benchmark files (it has to exit non-zero at once), the
# parent's wide sort operands beside the tree's narrow ones
# (scripts/setops_wide_narrow.py), an old cell traced on the parent, three
# more seeds.  Lines and standard error are kept under chiprun_out/pr48/.
T0=$(date +%s)
el() { echo $(( $(date +%s) - T0 )); }
O=chiprun_out/pr48; mkdir -p $O
CELL=setops_dedup_32m

cell() {   # cell <seed> <trace>: one run; non-zero where it failed or was not correct
  local seed=$1 trace=$2 t=$(date +%s)
  python3 benchmark/run.py --workload $CELL --seed $seed --seconds 46 --trace $trace \
      > $O/line.$seed.t$trace.json 2> $O/stderr.$seed.t$trace.txt
  local rc=$?
  echo "== seed $seed trace $trace: rc $rc, wall $(( $(date +%s) - t )) s (at $(el) s of the call)"
  grep -E "tables from|tables on the device|warm-up|routes:|window:|queries, ms|compiles before|own checks|result pulled|OVER" \
      $O/stderr.$seed.t$trace.txt | cut -c1-400
  python3 - $O/line.$seed.t$trace.json <<'PY'
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
  [ $rc -eq 0 ] && grep -q '"correct": true' $O/line.$seed.t$trace.json
}

for pair in 1:0 2:0 3:0 4800000011:1; do
  cell ${pair%%:*} ${pair##*:} || { echo "STOP"; tail -40 $O/stderr.${pair%%:*}.t${pair##*:}.txt | cut -c1-600; exit 1; }
done
echo "#### parent, new cell (at $(el) s)"
( cd _parent; s=$(date +%s)
  timeout 900 python3 benchmark/run.py --workload $CELL --seed 1 --seconds 46 --trace 0 \
      > ../$O/parent_newcell.out 2> ../$O/parent_newcell.err
  echo "parent, new cell: rc $? in $(( $(date +%s) - s )) s; stdout bytes $(wc -c < ../$O/parent_newcell.out)"
  tail -4 ../$O/parent_newcell.err | cut -c1-400 )
echo "#### wide against narrow (at $(el) s)"
timeout 1200 python3 scripts/setops_wide_narrow.py subtract 2> $O/wide_narrow.err || tail -20 $O/wide_narrow.err
echo "#### parent, an old cell traced under this tree's benchmark files (at $(el) s)"
( cd _parent; s=$(date +%s)
  timeout 1200 python3 benchmark/run.py --workload groupby_sort_25m --seed 4800000055 --seconds 46 --trace 1 \
      > ../$O/parent_oldcell.out 2> ../$O/parent_oldcell.err
  echo "parent, groupby_sort_25m traced: rc $? in $(( $(date +%s) - s )) s"
  tail -c 2500 ../$O/parent_oldcell.out )
echo "#### more seeds (at $(el) s)"
for seed in 4800000022 4800000033 4800000044; do
  if [ $(el) -lt 3200 ]; then cell $seed 0; else echo "SKIP $seed at $(el) s"; fi
done
echo "#### the whole call: $(el) s"
