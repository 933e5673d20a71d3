"""chip_smoke.py — the join -> groupby main path on the chip, checked.

The quickest proof that the system still starts on a TPU.  ONE process
(a parent that has touched JAX holds the chip), started from a shell
that has not touched JAX:

    python chip_smoke.py [--rows N] [--seed S]      # one chip
    python chip_smoke.py --chips 4 [--rows N]       # one host, four chips

One chip (the default, what the driver runs): the benchmark's workload — two
tables of two int64 columns, keys uniform in ``[0, 0.9 n)`` — at 32M rows
per side (the resident in-HBM regime), through the public entry points:

* resident phase: ``join_tables(how="inner")`` ->
  ``groupby_aggregate(sum a, sum b)``, pulled to the host and compared
  EXACTLY with ``pandas.merge(...).groupby(...).sum()``;
* pipelined phase: the same device tables through
  ``exec.pipelined_join(n_chunks=4, sink=GroupBySink)``, bit-equal with
  the resident result; the input tables must still be readable after it
  (buffer donation is real on the chip).

It fails if anything degraded on the way: a recovery event, a spill or
checkpoint event, a compile in a warm call, or a resident grouped reduce
that was eligible for the windowed Pallas gather and did not go through it.

``--chips 4`` runs the distributed ``join_tables`` + ``groupby_aggregate``
over ``TPUConfig(world_size=4)`` at 2^23 rows per chip per side against
the same pandas reference, and no other phase.  It passes since PR 28 (my
chip runs, PR 28, four TPU v5 lite of one host, from ``git archive`` copies
of the tree; the final tree's run with ``--seed 5``): exact against pandas
over 13,585,255 groups, 134,217,728 rows through the exchange in two calls,
off-diagonal share 0.7500, no compile in the warm call (3.09 s), no
recovery event, peak 1.34 GB a chip.  Cold compile seconds by builder
(the last line but one of a run; an empty cache, an earlier tree of PR 28
whose exchange and join programs are the final one's): ``join__count_fn``
90.5, ``shuffle__prep_fn`` 17.5, ``common__key_sample_fn`` 1.5,
``shuffle__hash_targets_fn`` and ``shuffle__round_fn`` 1.2 each, the rest
0.1; the final tree's ``fused__fused_fn`` 58.1 for its two dispatches (512
segment slots, then the settled space).  On the parent of PR 28 the same
command died in XLA:TPU's compile of the fused program (exit 139).

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
No accelerator: non-zero exit and no result line.  A phase that raises
ends the run with its traceback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: no default below 2^24 rows per side; the default is the resident regime
MIN_ROWS = 1 << 24
DEFAULT_ROWS = 32_000_000
DEFAULT_ROWS_PER_CHIP_4 = 1 << 23
N_CHUNKS = 4
AGGS = [("a", "sum"), ("b", "sum")]


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(cond, msg) -> None:
    """Not ``assert``: these checks are the script, and stay under -O."""
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# data + reference
# ---------------------------------------------------------------------------

def make_inputs(rows: int, seed: int, unique: float = 0.9) -> dict:
    """The benchmark's workload: keys uniform in [0, unique * rows)."""
    import numpy as np
    max_val = max(int(rows * unique), 1)
    rng = np.random.default_rng(seed)
    return {
        "lk": rng.integers(0, max_val, rows).astype(np.int64),
        "a": rng.integers(0, max_val, rows).astype(np.int64),
        "rk": rng.integers(0, max_val, rows).astype(np.int64),
        "b": rng.integers(0, max_val, rows).astype(np.int64),
    }


def reference(inp: dict):
    """The plain reference: pandas merge -> groupby -> sum, sorted by key.
    Returns (frame[k, a_sum, b_sum], joined row count)."""
    import pandas as pd
    left = pd.DataFrame({"k": inp["lk"], "a": inp["a"]})
    right = pd.DataFrame({"k": inp["rk"], "b": inp["b"]})
    j = pd.merge(left, right, on="k", how="inner")
    g = j.groupby("k", sort=True)[["a", "b"]].sum().reset_index()
    g.columns = ["k", "a_sum", "b_sum"]
    return g, len(j)


def build_tables(env, inp: dict):
    import cylon_tpu as ct
    lt = ct.Table.from_pydict({"k": inp["lk"], "a": inp["a"]}, env)
    rt = ct.Table.from_pydict({"k": inp["rk"], "b": inp["b"]}, env)
    return lt, rt


def _sorted_frame(table):
    df = table.to_pandas()
    return df.sort_values("k", kind="stable").reset_index(drop=True)


def check_equal_exact(got, want, what: str) -> None:
    """Exact (int64) equality of two k-sorted frames, column by column."""
    import numpy as np
    check(list(got.columns) == list(want.columns),
          f"{what}: columns {list(got.columns)} != {list(want.columns)}")
    check(len(got) == len(want),
          f"{what}: {len(got)} groups != reference {len(want)}")
    for c in want.columns:
        g = np.asarray(got[c])
        w = np.asarray(want[c])
        check(g.dtype == np.int64, f"{what}: column {c} is {g.dtype}")
        if not np.array_equal(g, w):
            bad = int(np.flatnonzero(g != w)[0])
            raise SmokeFailure(
                f"{what}: column {c} differs from the reference at sorted "
                f"row {bad}: {g[bad]} != {w[bad]}")


# ---------------------------------------------------------------------------
# what a phase reports, and what counts as degraded
# ---------------------------------------------------------------------------

def _plan_routes(qplan) -> list:
    """(op, route, n_chunks/n_ranges) of every plan node, pre-order."""
    out = []

    def walk(d):
        attrs = d.get("attrs") or {}
        if "route" in attrs:
            out.append({k: attrs[k] for k in
                        ("route", "n_chunks", "n_ranges", "shape_family")
                        if k in attrs} | {"op": d.get("op")})
        for c in d.get("children", ()):
            walk(c)
    for root in qplan.to_dict()["roots"]:
        walk(root)
    return out


def _timed_calls(step):
    """Cold call, then one warm call; returns (result, cold_s, warm_s,
    compiles_in_warm_call)."""
    from cylon_tpu.exec import compiler
    t0 = time.perf_counter()
    step()
    cold = time.perf_counter() - t0
    before = compiler.stats()["compile_events"]
    t0 = time.perf_counter()
    res = step()
    warm = time.perf_counter() - t0
    return res, cold, warm, compiler.stats()["compile_events"] - before


def _sync(table) -> None:
    from cylon_tpu.utils.host import sync_pull
    sync_pull(next(iter(table.columns.values())).data)


def gather_variants(env, skip=()) -> list:
    """What each fused join->groupby callsite of this env settled on:
    relational/fused._SEG_CACHE holds (segment bucket, windowed allowed,
    window) per callsite signature (sig[0] is the env serial).  ``skip``:
    signatures to leave out (those an earlier phase made)."""
    from cylon_tpu.relational import fused
    return [{"sig": k, "segment_space": int(v[0]), "window": int(v[2]),
             "windowed_allowed": bool(v[1]),
             "variant": f"windowed_pallas(w={v[2]})" if v[2]
             else "xla_gather"}
            for k, v in fused._SEG_CACHE.items()
            if k[0] == env.serial and isinstance(v, tuple)
            and k not in skip]


def check_not_degraded(where: str) -> None:
    """No recovery event, no spill, no disk page, no checkpoint."""
    from cylon_tpu.exec import checkpoint, memory, recovery
    ev = recovery.recovery_events()
    check(not ev, f"{where}: recovery events {ev}")
    mem, ck = memory.stats(), checkpoint.stats()
    for k in ("spill_events", "disk_events"):
        check(not mem[k], f"{where}: {k}={mem[k]}")
    check(not ck["checkpoint_events"],
          f"{where}: checkpoint_events={ck['checkpoint_events']}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def resident_phase(env, lt, rt, ref, ref_join_rows: int) -> dict:
    """join_tables -> groupby_aggregate on the resident tables, exact
    against the pandas reference."""
    from cylon_tpu import obs
    from cylon_tpu.relational import fused, groupby_aggregate, join_tables

    def step():
        j = join_tables(lt, rt, "k", "k", how="inner")
        g = groupby_aggregate(j, "k", AGGS)
        _sync(g)
        return g

    g, cold, warm, warm_compiles = _timed_calls(step)
    got = _sorted_frame(g)
    check_equal_exact(got, ref, "resident phase")

    # the route, from one more (profiled) call: EXPLAIN ANALYZE's tree
    qplan = obs.explain_analyze(step, profile_keys=False)
    routes = _plan_routes(qplan)
    variants = gather_variants(env)
    check(len(variants) == 1,
          f"expected one fused join->groupby callsite, found {variants}")
    variant = {k: v for k, v in variants[0].items() if k != "sig"}
    # relational/fused's own rule, at the density the reference shows
    eligible = fused.window_for(
        env.mesh, variant["segment_space"],
        len(ref) / (lt.row_count + rt.row_count)) > 0
    info = {"phase": "resident", "rows_in": [lt.row_count, rt.row_count],
            "join_rows": ref_join_rows, "groups": len(got),
            "cold_s": cold, "warm_s": warm,
            "compiles_in_warm_call": warm_compiles, "routes": routes,
            "gather": variant, "windowed_eligible": eligible}
    say(json.dumps(info))
    check(any(r["route"] == "fused_pushdown" for r in routes),
          f"resident phase did not take the fused pushdown: {routes}")
    check(warm_compiles == 0,
          f"resident phase: {warm_compiles} compile(s) in the warm call")
    if eligible:
        check(variant["window"] > 0,
              f"eligible for the windowed Pallas gather, ran {variant}")
    check_not_degraded("resident phase")
    return {"frame": got, "info": info}


def pipelined_phase(env, lt, rt, want, inp: dict) -> dict:
    """The same device tables through the range pipeline and the groupby
    sink, bit-equal with the resident result; the inputs stay readable."""
    import numpy as np
    from cylon_tpu import obs
    from cylon_tpu.exec import GroupBySink, pipelined_join

    def step():
        sink = GroupBySink("k", AGGS)
        pipelined_join(lt, rt, "k", "k", how="inner", n_chunks=N_CHUNKS,
                       sink=sink)
        g = sink.finalize()
        _sync(g)
        return g

    before = {v["sig"] for v in gather_variants(env)}
    g, cold, warm, warm_compiles = _timed_calls(step)
    got = _sorted_frame(g)
    check_equal_exact(got, want, "pipelined phase")
    pieces = sorted({(v["segment_space"], v["variant"])
                     for v in gather_variants(env, skip=before)})
    qplan = obs.explain_analyze(step, profile_keys=False)
    routes = _plan_routes(qplan)
    info = {"phase": "pipelined", "n_chunks": N_CHUNKS,
            "groups": len(got), "cold_s": cold, "warm_s": warm,
            "compiles_in_warm_call": warm_compiles, "routes": routes,
            "piece_gathers": pieces}
    say(json.dumps(info))
    check(any(r["route"] == "range_pipeline" for r in routes),
          f"pipelined phase did not take the range pipeline: {routes}")
    check(warm_compiles == 0,
          f"pipelined phase: {warm_compiles} compile(s) in the warm call")
    # donation consumed scratch, never the caller's tables
    for t, kname, vname, vcol in ((lt, "lk", "a", "a"), (rt, "rk", "b", "b")):
        df = t.to_pandas()
        check(np.array_equal(np.asarray(df["k"]), inp[kname])
              and np.array_equal(np.asarray(df[vcol]), inp[vname]),
              "an input table is not readable after the pipelined phase")
    say("input tables still readable after the pipelined phase")
    check_not_degraded("pipelined phase")
    return {"frame": got, "info": info}


def distributed_phase(env, lt, rt, ref, rows_per_chip: int) -> dict:
    """shuffle -> local join -> groupby over the mesh, exact against the
    pandas reference; rows spread over every device and really moved."""
    import numpy as np
    from cylon_tpu import obs
    from cylon_tpu.obs import comm
    from cylon_tpu.relational import groupby_aggregate, join_tables

    w = env.world_size
    for name, t in (("left", lt), ("right", rt)):
        vc = np.asarray(t.valid_counts, np.int64)
        check(vc.shape == (w,) and vc.sum() == t.row_count, (name, vc))
        check(vc.min() >= 0.9 * rows_per_chip,
              f"{name} table is not spread over devices: {vc.tolist()}")
        col = next(iter(t.columns.values())).data
        devs = {sh.device for sh in col.addressable_shards}
        sizes = {sh.data.shape for sh in col.addressable_shards}
        check(len(devs) == w and len(sizes) == 1,
              f"{name} table: {len(devs)} device(s), shards {sizes}")
        say(f"{name}: valid_counts={vc.tolist()} on {len(devs)} devices, "
            f"shard shape {next(iter(sizes))}")

    exch = obs.counter("exchange_rows_total")
    exch_before = exch.value
    comm.arm(True)
    comm.reset()

    def step():
        j = join_tables(lt, rt, "k", "k", how="inner")
        g = groupby_aggregate(j, "k", AGGS)
        _sync(g)
        return g

    g, cold, warm, warm_compiles = _timed_calls(step)
    rep = comm.report()
    comm.arm(False)
    got = _sorted_frame(g)
    check_equal_exact(got, ref, "distributed phase")
    moved = exch.value - exch_before
    routes = _plan_routes(obs.explain_analyze(step, profile_keys=False))
    m = np.asarray(rep["rows"], np.int64) if rep else np.zeros((w, w))
    off = float(m.sum() - np.trace(m)) / max(float(m.sum()), 1.0)
    info = {"phase": "distributed", "world": w,
            "rows_per_chip": rows_per_chip, "groups": len(got),
            "cold_s": cold, "warm_s": warm,
            "compiles_in_warm_call": warm_compiles,
            "exchange_rows_total": int(moved),
            "off_diagonal_share": off, "routes": routes}
    say(json.dumps(info))
    check(moved > 0, "no row went through the exchange")
    check(rep and 0.5 < off < 0.95,
          f"exchange did not cross devices as a uniform hash would: {off}")
    check(warm_compiles == 0,
          f"distributed phase compiled {warm_compiles} program(s) warm")
    check_not_degraded("distributed phase")
    return {"frame": got, "info": info}


# ---------------------------------------------------------------------------
# process set-up
# ---------------------------------------------------------------------------

def _cache_entries(d: str) -> int:
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per side (one chip; default 32M, at least "
                         "2^24) or per chip per side (--chips 4; default "
                         "2^23)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()   # no platform set: jax takes the accelerator
    say(f"jax {jax.__version__}; devices: {devs}")
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found — jax reports platform "
              f"{devs[0].platform!r} ({devs[0].device_kind}); this script "
              "runs on the chip only", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, jax reports {len(devs)}", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": args.chips}

    import cylon_tpu as ct
    from cylon_tpu import config
    from cylon_tpu.ctx.context import TPUConfig
    from cylon_tpu.exec import compiler, memory, recovery
    from cylon_tpu.native import native_available

    cache_dir = config.jax_cache_dir()
    say(f"native string hash built: {native_available()}")
    say(f"jax compilation cache: {cache_dir or 'off'} "
        f"({_cache_entries(cache_dir)} entries before the run)")
    compiler.install_listener()
    recovery.reset_events()
    memory.reset_stats()

    env = ct.CylonEnv(config=TPUConfig(world_size=args.chips))
    if args.chips == 1:
        rows = DEFAULT_ROWS if args.rows is None else args.rows
        if rows < MIN_ROWS:
            print(f"chip_smoke: --rows {rows} is below the resident size "
                  f"this script checks (at least {MIN_ROWS})",
                  file=sys.stderr)
            return 1
        total = rows
    else:
        rows = DEFAULT_ROWS_PER_CHIP_4 if args.rows is None else args.rows
        total = rows * args.chips
    say(f"chips={args.chips} rows per side={total} seed={args.seed}")

    t0 = time.perf_counter()
    inp = make_inputs(total, args.seed)
    ref, join_rows = reference(inp)
    say(f"pandas reference: {join_rows} joined rows, {len(ref)} groups "
        f"({time.perf_counter() - t0:.1f} s on the host)")
    t0 = time.perf_counter()
    lt, rt = build_tables(env, inp)
    _sync(lt), _sync(rt)
    say(f"tables on the device in {time.perf_counter() - t0:.1f} s")

    if args.chips == 1:
        res = resident_phase(env, lt, rt, ref, join_rows)
        pipelined_phase(env, lt, rt, res["frame"], inp)
    else:
        distributed_phase(env, lt, rt, ref, rows)

    for d in devs[:args.chips]:
        st = d.memory_stats() or {}
        say(f"{d}: peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"bytes_limit={st.get('bytes_limit')}")
    cst = compiler.stats()
    say(f"compiles: {cst['compile_events']} in {cst['compile_seconds']:.1f} s;"
        f" cache {cache_dir or 'off'} holds {_cache_entries(cache_dir)} "
        "entries after the run")
    say("compile seconds by builder: " + json.dumps(
        {b: round(v["seconds"], 1) for b, v in cst["by_builder"].items()}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
