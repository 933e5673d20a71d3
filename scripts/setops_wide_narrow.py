"""One process on the chip: the cell's tables, `subtract` (count + materialize
programs) and `unique` with the columns' bounds known (int32-narrow sort
operands: the tree's form) and with the bounds dropped (a (hi, lo) pair a
column: the parent's form).  Cold compile seconds and warm seconds of each."""
import json, os, sys, time
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmark")]
from lib import files, generate, tables as device_tables
import jax
import cylon_tpu as ct
from cylon_tpu.ctx.context import TPUConfig
from cylon_tpu.exec import compiler
from cylon_tpu.relational import set_operation, unique_table

BENCH = os.path.join(REPO, "benchmark")
cfg = files.load_json(BENCH, "configs", "cylon_setops_dedup_32m")
which = sys.argv[1:] or ["subtract"]
compiler.install_listener()
env = ct.CylonEnv(config=TPUConfig(world_size=1))
host = generate.host_tables(BENCH, cfg, 4800000099)
out = {"device": jax.devices()[0].device_kind}
for form in ("narrow", "wide"):
    t = device_tables.from_host(env, host)
    if form == "wide":
        for tab in t.values():
            for c in tab.columns.values():
                c.bounds = None
    device_tables.ready(*t.values())
    for op in which:
        def call():
            r = unique_table(t["a"], subset=["k"]) if op == "unique" \
                else set_operation(t["a"], t["b"], op)
            device_tables.ready(r)
            return r.row_count
        c0 = compiler.stats()["compile_seconds"]
        t0 = time.perf_counter(); rows = call(); first = time.perf_counter() - t0
        compile_s = compiler.stats()["compile_seconds"] - c0
        warm = []
        for _ in range(3):
            t0 = time.perf_counter(); call(); warm.append(time.perf_counter() - t0)
        out[f"{op}.{form}"] = {"rows_out": rows, "first_call_s": round(first, 2),
                               "compile_s": round(compile_s, 2),
                               "warm_s": [round(w, 4) for w in warm]}
        print(json.dumps({f"{op}.{form}": out[f"{op}.{form}"]}), flush=True)
    del t
os.makedirs(os.path.join(REPO, "chiprun_out", "pr48"), exist_ok=True)
with open(os.path.join(REPO, "chiprun_out", "pr48", "wide_narrow.json"), "w") as f:
    json.dump(out, f, indent=1)
