"""One process on the chip: the cell's tables at a seed, then the three
operators of ``setops_dedup_32m`` under EXPLAIN ANALYZE - what each
materialize dispatch said (plan node ``path`` / ``window`` / ``density`` /
``max_tile_span``, ``setop_mat_dispatches``) - and three warm calls of each
on the host's clock.  ``python scripts/setops_dispatch.py <seed> ...``"""
import json, os, sys, time
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmark")]
from lib import files, generate, tables as device_tables
import jax
import cylon_tpu as ct
from cylon_tpu import obs
from cylon_tpu.ctx.context import TPUConfig
from cylon_tpu.obs import metrics
from cylon_tpu.relational import set_operation, unique_table

BENCH = os.path.join(REPO, "benchmark")
cfg = files.load_json(BENCH, "configs", "cylon_setops_dedup_32m")
env = ct.CylonEnv(config=TPUConfig(world_size=1))
out = {"device": jax.devices()[0].device_kind}
for seed in [int(s) for s in sys.argv[1:]] or [1]:
    t = device_tables.from_host(env, generate.host_tables(BENCH, cfg, seed))
    device_tables.ready(*t.values())
    calls = {"unique": lambda: unique_table(t["a"], subset=["k"]),
             "union": lambda: set_operation(t["a"], t["b"], "union"),
             "subtract": lambda: set_operation(t["a"], t["b"], "subtract")}
    for op, call in calls.items():
        def ready():
            r = call()
            device_tables.ready(r)
            return r.row_count
        node = obs.explain_analyze(ready, profile_keys=False).roots[0]
        warm = []
        for _ in range(3):
            t0 = time.perf_counter(); rows = ready()
            warm.append(round(time.perf_counter() - t0, 4))
        out[f"{seed}.{op}"] = {"rows_out": rows, "warm_s": warm, **{
            k: node.attrs.get(k) for k in ("path", "window", "density",
                                           "max_tile_span")}}
        print(json.dumps({f"{seed}.{op}": out[f"{seed}.{op}"]}), flush=True)
    del t
out["setop_mat_dispatches"] = {k: v for k, v in metrics.snapshot().items()
                               if k.startswith("setop_mat_dispatches") and v}
print(json.dumps(out["setop_mat_dispatches"]), flush=True)
os.makedirs(os.path.join(REPO, "chiprun_out", "pr49"), exist_ok=True)
with open(os.path.join(REPO, "chiprun_out", "pr49", "dispatch.json"), "w") as f:
    json.dump(out, f, indent=1)
