"""How a grouped integer ``sum`` is scanned (ISSUE 40): the exact int64
prefix of values that fit 32 bits in 32-bit scans
(``ops/groupby.carried_cumsum32``), the one rule that picks the form from
a column's dtype and ``Column.bounds`` (``relational/groupby.sum_scan_form``),
the three forms end to end through every route that reaches
``grouped_reduce``, what the programs hold, and what the registry and the
plan node say.  CPU: results, program text and counts - never a time."""

import json
import os
import re
import sys

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu import obs
from cylon_tpu.core.column import Column
from cylon_tpu.core.table import Table
from cylon_tpu.obs import metrics
from cylon_tpu.ops import groupby as gbk
from cylon_tpu.relational import groupby as rel_gb
from cylon_tpu.relational import groupby_aggregate, join_tables

I32 = (1 << 31) - 1


def _values(case: str, rng) -> tuple:
    """(int64 values that fit int32, live rows): the shapes ISSUE 40
    names."""
    if case == "bounds_at_int32_max":       # every step carries or borrows
        x = rng.choice(np.array([I32, -I32, -I32 - 1]), 5000)
    elif case == "all_negative":
        x = -rng.integers(1, 1 << 25, 4097)
    elif case == "mixed_sign":
        x = rng.integers(-(1 << 30), 1 << 30, 128 * 128 + 7)
    elif case == "not_a_multiple_of_the_block":
        x = rng.integers(0, 28_800_000, 129)
    elif case == "sum_passes_2_32":         # 9 values of ~2^30
        x = rng.integers(1 << 29, 1 << 30, 9)
    elif case == "sum_passes_2_40":         # the Zipf cell's hot key does
        x = rng.integers(1 << 30, I32, 3001)
    elif case == "one_row":
        x = np.array([-7])
    else:
        assert case == "masked_tail"        # garbage past the live rows
        x = rng.integers(0, 28_800_000, 3000)
        return np.concatenate([x, rng.integers(1 << 40, 1 << 50, 1096)]), 3000
    return x.astype(np.int64), len(x)


CASES = ("bounds_at_int32_max", "all_negative", "mixed_sign",
         "not_a_multiple_of_the_block", "sum_passes_2_32", "sum_passes_2_40",
         "one_row", "masked_tail")


def _block_for(x) -> int:
    """The block the rule would give values like ``x`` (1: flat)."""
    return rel_gb.sum_scan_form(np.dtype(np.int64),
                                (int(x.min()), int(x.max())), 1 << 40).block


@pytest.mark.parametrize("world", ["env1", "env4"])
@pytest.mark.parametrize("case", CASES)
def test_prefix_in_32_bits_is_the_int64_cumsum(case, world, request, rng):
    """``carried_cumsum32``'s words are ``cumsum(x.astype(int64))``'s, bit
    for bit, per shard on one and on four devices, flat and in the blocks
    the bounds allow - masked as ``grouped_reduce`` masks (``where``
    before the narrowing cast, so what a dead row holds is never cast)."""
    from cylon_tpu.relational.common import ROW
    env = request.getfixturevalue(world)
    w = env.world_size
    shards = [_values(case, rng) for _ in range(w)]
    n_live = shards[0][1]
    x = np.concatenate([s[0] for s in shards])
    n = len(shards[0][0])

    live = np.where(np.arange(n) < n_live, x.reshape(w, n), 0)
    blocks = {1, _block_for(live)}
    assert blocks == {"bounds_at_int32_max": {1}, "sum_passes_2_40": {1},
                      "sum_passes_2_32": {1}, "mixed_sign": {1},
                      "one_row": {1, 128}}.get(case, {1, 64}), blocks

    def per_shard(v):
        mask = jnp.arange(n) < n_live
        v32 = jnp.where(mask, v, 0).astype(jnp.int32)
        return tuple(gbk.carried_cumsum32(v32, b) for b in sorted(blocks))

    outs = jax.jit(jax.shard_map(per_shard, mesh=env.mesh, in_specs=ROW,
                                 out_specs=ROW))(jnp.asarray(x))
    for hi, lo in outs:
        assert hi.dtype == lo.dtype == jnp.int32
        got = (np.asarray(hi).astype(np.int64) << 32) \
            | np.asarray(lo).astype(np.int64) & 0xFFFFFFFF
        assert np.array_equal(got.reshape(w, n), np.cumsum(live, axis=1))
    if case == "sum_passes_2_40":
        assert np.abs(got).max() > 1 << 40
    if case == "sum_passes_2_32":
        assert np.abs(got).max() > 1 << 32


@pytest.mark.parametrize("dtype,bounds,rows,want", [
    ("int64", (0, 28_799_999), 65_011_712, "val32/64"),  # the 32M cells'
    ("int64", (0, 22_499_999), 25_165_824, "val32/64"),  # groupby_sort_25m
    ("int64", (0, 7_549_746), 17_825_792, "val32/128"),  # the four-chip ones
    ("int64", (-(1 << 24) + 1, 0), 1 << 20, "val32/128"),
    ("int64", (0, 1 << 24), 1 << 20, "val32/64"),
    ("int64", (-(1 << 25), 0), 1 << 20, "val32"),
    ("int64", (0, 28_799_999), 64, "sum32"),
    ("int64", (-I32 - 1, I32), 2, "val32"),
    ("int64", (0, I32 + 1), 2, "pair64"),
    ("int64", (-I32 - 2, 0), 2, "pair64"),
    ("uint64", (0, 1 << 40), 2, "pair64"),
    ("uint32", (0, I32), 1 << 20, "val32"),
    ("int32", (-5, 5), 1 << 20, "sum32"),
    ("int64", None, 2, "pair64"),
    ("float64", (0, 1), 2, "pair64"),
])
def test_the_rule_reads_dtype_and_bounds_alone(dtype, bounds, rows, want):
    """One of three words, and for ``val32`` the largest measured block
    whose sums the bounds prove to fit int32; with ``two_lanes`` never
    ``sum32``."""
    got = rel_gb.sum_scan_form(np.dtype(dtype), bounds, rows)
    assert str(got) == want and got.form in gbk.SUM_FORMS
    assert got.block == 1 or got.block in gbk.VAL32_BLOCKS
    two = rel_gb.sum_scan_form(np.dtype(dtype), bounds, rows, True)
    assert two == got if got.form != "sum32" else two.form == "val32"


def _without_bounds(t: Table) -> Table:
    """``t`` as a derived table is: the same device arrays, no host-known
    bounds on any column."""
    return Table({n: Column(c.data, c.type, c.validity, c.dictionary)
                  for n, c in t.columns.items()}, t.env, t.valid_counts)


def _sum_scans() -> dict:
    return {f: metrics.counter("grouped_sum_scans", form=f).value
            for f in gbk.SUM_FORMS}


def _delta(before: dict) -> dict:
    return {f: v - before[f] for f, v in _sum_scans().items()
            if v != before[f]}


#: case -> (value range, how the table is handed over, the descriptor)
_FORMS = {
    "sum32": ((-60, 60), lambda t: t, "sum32"),
    "val32_flat": ((-I32, I32), lambda t: t, "val32"),
    "val32_b64": ((-(1 << 25) + 1, (1 << 24) + 5), lambda t: t, "val32/64"),
    "val32_b128": ((-5, (1 << 24) - 1), lambda t: t, "val32/128"),
    "pair64_no_bounds": ((-I32, I32), _without_bounds, "pair64"),
    "pair64_wide": ((-(1 << 40), 1 << 40), lambda t: t, "pair64"),
}


@pytest.mark.parametrize("route,world", [("raw", "env1"), ("combine", "env4"),
                                         ("fused", "env1"),
                                         ("fused", "env4")])
@pytest.mark.parametrize("form", list(_FORMS))
def test_equal_tables_under_each_form(form, route, world, request):
    """``sum(a) by k`` through the standalone groupby (route ``raw`` on one
    device, ``combine_shuffle`` on four) and through the fused
    join->groupby, with the value column's bounds making each of the
    three words - and none at all: every answer pandas', the registry
    counting one scan an integer sum under the form's label (the
    distributed combine: ``sum32`` rides two lanes there, so ``val32``;
    and its phase 2 sums partial sums, ``pair64``), and the groupby plan
    node saying ``sum_scan``."""
    env = request.getfixturevalue(world)
    rng = np.random.default_rng(len(form) * 131 + len(route))
    (lo, hi), hand, shown = _FORMS[form]
    n = 2999
    ldf = pd.DataFrame({"k": rng.integers(0, 700, n),
                        "a": rng.integers(lo, hi, n, endpoint=True)})
    ldf.loc[0, "a"], ldf.loc[1, "a"] = lo, hi
    rdf = pd.DataFrame({"k": rng.integers(0, 700, n),
                        "b": rng.integers(lo, hi, n, endpoint=True)})
    lt = hand(ct.Table.from_pandas(ldf, env))
    rt = hand(ct.Table.from_pandas(rdf, env))
    word = shown.partition("/")[0]
    before = _sum_scans()
    if route == "fused":
        aggs = [("a", "sum"), ("b", "sum")]
        want = ldf.merge(rdf, on="k").groupby("k", as_index=False).agg(
            a_sum=("a", "sum"), b_sum=("b", "sum"))
        plan = obs.explain_analyze(lambda: groupby_aggregate(
            join_tables(lt, rt, "k", "k", how="inner"), "k", aggs))
        counted = {word: 2}
    else:
        aggs = [("a", "sum")]
        want = ldf.groupby("k", as_index=False).agg(a_sum=("a", "sum"))
        plan = obs.explain_analyze(lambda: groupby_aggregate(lt, "k", aggs))
        if route == "combine" and word == "sum32":
            word, shown = "val32", "val32/128"
        counted = {word: 1}
        if route == "combine":
            counted["pair64"] = counted.get("pair64", 0) + 1
    got = plan.result.to_pandas().sort_values("k").reset_index(drop=True)
    pd.testing.assert_frame_equal(
        got, want.sort_values("k").reset_index(drop=True), check_dtype=False)
    assert got["a_sum"].dtype == np.int64
    assert _delta(before) == counted
    node, = [x for x in _nodes(plan.to_dict()["roots"])
             if x["op"] == "groupby"]
    assert node["attrs"]["route"] == {
        "raw": "raw", "combine": "combine_shuffle",
        "fused": "fused_pushdown"}[route]
    assert tuple(node["attrs"]["sum_scan"]) == (shown,) * len(aggs)


def _nodes(roots):
    for r in roots:
        yield r
        yield from _nodes(r.get("children", ()))


def test_the_counter_counts_integer_sums_alone(env1, rng):
    """One count an integer ``sum`` a dispatch: a float sum, a ``mean``, a
    ``count`` and a ``max`` of the same call scan no integer sum and bump
    nothing; a second call counts again."""
    assert {f'grouped_sum_scans{{form="{f}"}}' for f in gbk.SUM_FORMS} \
        <= set(metrics.snapshot())              # registered at import
    n = 1800
    t = ct.Table.from_pydict({
        "k": rng.integers(0, 99, n), "a": rng.integers(0, 1 << 30, n),
        "c": rng.integers(-9, 9, n), "f": rng.normal(size=n)}, env1)
    before = _sum_scans()
    aggs = [("a", "sum"), ("f", "sum"), ("a", "mean"), ("a", "count"),
            ("c", "max"), ("c", "sum")]
    got = groupby_aggregate(t, "k", aggs).to_pandas()
    assert _delta(before) == {"val32": 1, "sum32": 1}
    groupby_aggregate(t, "k", [("f", "sum"), ("a", "var")])
    assert _delta(before) == {"val32": 1, "sum32": 1}
    groupby_aggregate(t, "k", aggs)
    assert _delta(before) == {"val32": 2, "sum32": 2}
    want = t.to_pandas().groupby("k").agg(a=("a", "sum"), c=("c", "sum"))
    got = got.sort_values("k")
    assert np.array_equal(got["a_sum"], want["a"]) \
        and np.array_equal(got["c_sum"], want["c"])


def test_a_fused_query_frees_its_join_state_without_the_collector(env1, rng):
    """The fused callsite asks the join's output plan for types and bounds
    through stand-ins that hold no reference to the ``JoinState``: with the
    cyclic collector off, the sorted payload of one query (a GiB at the
    cells' size) is gone before the next one runs.  A class defined per
    call over ``state`` is cyclic garbage that keeps it until a
    generation-2 collection - on the chip that read as a device OOM at the
    cell's twelfth query (PERF.md PR 40)."""
    import gc
    n = 3000
    lt = ct.Table.from_pydict({"k": rng.integers(0, 500, n),
                               "a": rng.integers(0, 1 << 24, n)}, env1)
    rt = ct.Table.from_pydict({"k": rng.integers(0, 500, n),
                               "b": rng.integers(0, 1 << 24, n)}, env1)

    def query():
        groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"), "k",
                          [("a", "sum"), ("b", "sum")])

    query(), query()                    # settle the callsite's caches
    gc.collect()
    gc.disable()
    try:
        before = len(jax.live_arrays())
        for _ in range(3):
            query()
        assert len(jax.live_arrays()) == before
    finally:
        gc.enable()


def _wide_scans(traced) -> list:
    """(primitive, dtype, scanned length) of every scan-like equation of a
    64-bit type: what XLA:TPU lowers to an (hi, lo) pair scan - over the
    rows where the length is, in blocks of 128 where a mesh's program
    writes ``blocked_cumsum``."""
    from cylon_tpu.analysis.jaxpr_check import iter_eqns
    found = []
    for e, _ in iter_eqns(traced):
        name = e.primitive.name
        if not (name.startswith("cum") or name.startswith("reduce_window")):
            continue
        aval = e.outvars[0].aval
        if np.dtype(aval.dtype).itemsize == 8:
            found.append((name, str(aval.dtype),
                          aval.shape[e.params.get("axis", 0)]))
    return found


@pytest.mark.parametrize("world", ["env1", "env4"])
@pytest.mark.parametrize("program", ["fused", "raw", "combine"])
def test_val32_programs_hold_no_row_length_64_bit_scan(program, world,
                                                       request):
    """The jaxpr of the fused, the raw and the combine program with every
    sum ``val32`` - the benchmark cells' schema: int64 within int32 bounds
    - holds no ``cumsum`` / ``reduce_window`` of a 64-bit type at all,
    with the sums flat or in blocks, on one device or four; under
    ``pair64`` the same builders do (flat on one device, in blocks of 128
    on a mesh)."""
    from cylon_tpu.analysis import registry
    from cylon_tpu.ops import join as joink, lanes
    from cylon_tpu.relational import fused
    env = request.getfixturevalue(world)
    w, cap = env.world_size, 2048
    S = jax.ShapeDtypeStruct

    def traced(form):
        if program == "fused":
            lspec = lanes.plan_lanes(("int64", "int64"), (False, False),
                                     (True, True))
            rspec = lanes.plan_lanes(("int64",), (False,), (True,))
            layout = joink.payload_layout(lspec, rspec, (0,), ("int64",),
                                          (False,), (True,), False)
            fn = fused._fused_fn(
                env.mesh, cap, False, lspec, rspec, layout,
                (("l", 1, "sum"), ("r", 0, "sum")), (0,), (True,), 512, 1,
                sum_forms=(form, form))
            vc, row = S((w,), np.int64), S((w * 2 * cap,), np.int32)
            pl = (row,) * len(layout.kept_keys) \
                + (S((w * 2 * cap,), np.uint32),) * layout.n_payloads
            return jax.make_jaxpr(registry.unwrap(fn))(vc, vc, row, row, pl)
        vspec = lanes.plan_lanes(("int64", "int64"), (False, False),
                                 (True, True))
        col = S((w * cap,), np.int64)
        args = (S((w,), np.int32), (col,), (None,), (col,), (None,))
        if program == "raw":
            fn = rel_gb._raw_fn(env.mesh, (("sum", 0.5),), 512, 1, False,
                                (True,), (form,), vspec, (0,))
        else:
            fn = rel_gb._combine_fn(env.mesh, ("sum",), 512, False, (True,),
                                    (form,), vspec, (0,))
        return jax.make_jaxpr(registry.unwrap(fn))(*args)

    for block in (1,) + gbk.VAL32_BLOCKS:
        assert _wide_scans(traced(gbk.SumScan("val32", block))) == []
    wide = _wide_scans(traced(gbk.SumScan("pair64")))
    assert {dt for _p, dt, _n in wide} == {"int64"}
    rows = (2 if program == "fused" else 1) * cap
    long_ = [n for _p, _dt, n in wide if n > 128]
    if w == 1:
        assert long_ == [rows] * (2 if program == "fused" else 1), wide
    else:
        assert long_ == [] and wide, wide


def test_benchmark_reads_sum_scans_32bit_share(tmp_path, monkeypatch, capfd):
    """The yardstick's side of the counter: ``benchmark/metrics/
    sum_scans_32bit_share.json`` through ``run.py``'s own ``main`` on the
    join cell's 65,536-row twin (the benchmark's test helpers; the CPU has
    no device plane, so the trace reduction is stood in for as in
    ``tests/test_obs.py``): its patterns find the three series, the share
    is the 32-bit forms over all three, and the twin's bounded columns
    scan no sum in 64 bits."""
    import importlib.util
    bench_tests = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests")
    spec = importlib.util.spec_from_file_location(
        "_bench_test_helpers", os.path.join(bench_tests, "helpers.py"))
    helpers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(helpers)
    monkeypatch.setattr(sys, "path", list(sys.path))
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    name = "sum_scans_32bit_share"
    with open(os.path.join(bench_dir, "metrics", name + ".json")) as f:
        m = json.load(f)
    series = [k for k in metrics.snapshot() if k.startswith("grouped_sum")]
    assert sorted(k for k in series if re.search(m["args"]["per"], k)) \
        == sorted(series) and len(series) == 3
    assert sorted(k for k in series if re.search(m["args"]["counter"], k)) \
        == ['grouped_sum_scans{form="sum32"}',
            'grouped_sum_scans{form="val32"}']
    with open(os.path.join(bench_dir, "metrics", "tiny_" + name + ".json"),
              "w") as f:
        json.dump(dict(m, name="tiny_" + name,
                       workloads=["tiny_join_groupby_32m"]), f)
    run = helpers.load_run(bench_dir)
    helpers.steer_to_cpu(run, monkeypatch)
    monkeypatch.setattr(run, "_traced_queries", lambda one, n, spans, d: (
        [one() for _ in range(n)],
        {"n_queries": n, "n_chips": 1, "busy_s": 0.9, "window_s": 1.0,
         "idle_share": 0.1, "op_seconds": [], "gap_seconds": []})[1])
    before = _sum_scans()
    capfd.readouterr()
    rc = run.main(["--workload", "tiny_join_groupby_32m", "--seed",
                   str(2**31 + 40), "--seconds", "0.5", "--trace", "1"])
    out = capfd.readouterr()
    assert rc == 0, out.err[-3000:]
    line = helpers.last_json_line(out.out)
    assert line["correct"] is True, line["compared"]
    grew = _delta(before)
    assert set(grew) <= {"sum32", "val32"} and sum(grew.values()) >= 2
    after = _sum_scans()
    # the reader sums the whole process's registry: other tests' sums too
    assert line["metrics"]["tiny_" + name] == {
        "value": (after["sum32"] + after["val32"]) / sum(after.values()),
        "unit": "ratio"}
    if not any(before.values()):
        assert line["metrics"]["tiny_" + name]["value"] == 1.0
