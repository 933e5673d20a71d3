"""Names on the device (ISSUE 26, part A): every program carries its
builder's name, every row-scale operation a stage of the one vocabulary
(``cylon_tpu/utils/stages.py``).

(a) every declared builder (``analysis/registry.collect()``), traced at
its declaration's shapes: each row-scale equation sits under a
``cylon.<stage>`` whose stage is in the vocabulary — one case a builder;
(b) the two benchmark routes at toy size: the programs they launch are
named after their builders (HLO module ``jit_<module>_<builder>``) and the
sort / gather instructions carry the expected stage in
``op_name`` — which is what the profiler hands back as ``tf_op``; under
``segment_starts`` no program holds a scatter, and the four grouped
reduces hold ONE sort there, of one operand and not stable (ISSUE 33).
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu.analysis import registry
from cylon_tpu.analysis.jaxpr_check import _sub_jaxprs
from cylon_tpu.utils import stages
from cylon_tpu.utils.cache import short_name

_STAGE = re.compile(r"cylon\.([A-Za-z0-9_]+)")
DECLS = {d.builder: d for d in registry.collect()}


def _staged_eqns(jaxpr, inherited=()):
    """``(eqn, stages)`` of every leaf equation: ``stages`` are the
    ``cylon.<stage>`` names of the enclosing equations' name stacks and
    its own, outermost first."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        here = tuple(inherited) + tuple(
            _STAGE.findall(str(eqn.source_info.name_stack)))
        subs = list(_sub_jaxprs(eqn))
        if not subs:
            yield eqn, here
        for sub in subs:
            yield from _staged_eqns(sub, here)


def _unstaged(jaxpr):
    """``(primitive, stages)`` of every leaf equation with a row-scale
    output whose innermost stage (own name stack, else the enclosing
    equation's) is missing or not in the vocabulary."""
    bad = []
    for eqn, here in _staged_eqns(jaxpr):
        row_scale = any(
            int(np.prod(getattr(v.aval, "shape", ()) or (1,)))
            >= registry.ROW_SCALE_ELEMS for v in eqn.outvars)
        if row_scale and (not here or here[-1] not in stages.STAGES):
            bad.append((eqn.primitive.name, here))
    return bad


def test_vocabulary_is_closed_and_rooted():
    assert len(stages.STAGES) <= 40            # small and fixed
    assert set(stages.ROOT_OF_MODULE.values()) <= set(stages.STAGES)
    with pytest.raises(ValueError):
        stages.stage("not_a_stage")
    with pytest.raises(ValueError):
        stages.staged("not_a_stage")
    # every builder module has a root stage
    for name in DECLS:
        module = name.split("[")[0].rpartition(".")[0].rpartition(".")[2]
        assert module in stages.ROOT_OF_MODULE, name


@pytest.mark.parametrize("builder", sorted(DECLS))
def test_every_row_scale_equation_has_a_stage(builder, env4):
    traced = DECLS[builder].trace(env4.mesh)
    assert _unstaged(traced) == []


_CUMULATIVE = {"cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"}


def _long_wide_scans(traced) -> list:
    """Cumulative equations with a 64-bit operand and more than the 128
    elements XLA:TPU's scan rewriter leaves alone."""
    from cylon_tpu.analysis.jaxpr_check import iter_eqns
    out = []
    for eqn, _ in iter_eqns(traced):
        if eqn.primitive.name in _CUMULATIVE:
            aval = eqn.invars[0].aval
            if (np.dtype(aval.dtype).itemsize == 8
                    and aval.shape[eqn.params["axis"]] > 128):
                out.append((eqn.primitive.name, str(aval.dtype), aval.shape))
    return out


@pytest.mark.parametrize("builder", sorted(DECLS))
def test_multi_device_programs_hold_no_pile_of_long_wide_scans(builder, env4):
    """XLA:TPU's scan rewriter dies in-process (SIGSEGV) on a program for
    more than one device that holds about four long 64-bit scans - three
    compiled, four died, described ``v5e:2x2`` compiles at 17.8M rows a
    shard (PERF.md, PR 28).  ``ops/groupby.grouped_reduce``, whose scan
    count grows with a query's aggregations, writes them in blocks on
    such a mesh (``blocked_cumsum``): its builders hold none.  No other
    builder holds more than one."""
    scans = _long_wide_scans(DECLS[builder].trace(env4.mesh))
    module = builder.split("[")[0].rpartition(".")[0].rpartition(".")[2]
    assert len(scans) <= (0 if module in ("fused", "groupby") else 1), scans


@pytest.mark.parametrize("builder", sorted(DECLS))
def test_no_registered_builder_scatters_under_segment_starts(builder, env4):
    """``starts`` is a one-operand sort (ops/groupby.grouped_starts, PR
    33); the scatter it was cost 4.6-4.9 ns a candidate update on v5e."""
    traced = DECLS[builder].trace(env4.mesh)
    assert [e.primitive.name for e, st in _staged_eqns(traced)
            if "segment_starts" in st
            and e.primitive.name.startswith("scatter")] == []


# ---- (b) the benchmark's two routes at toy size ---------------------------

@pytest.fixture()
def launched(monkeypatch):
    """Every facade program called inside the test, with its first call's
    arguments: ``{label: (program, args, kwargs)}``."""
    from cylon_tpu.exec import compiler
    seen = {}
    real = compiler._Program.__call__

    def spy(self, *args, **kwargs):
        seen.setdefault(self._fn.__name__, (self, args, kwargs))
        return real(self, *args, **kwargs)

    monkeypatch.setattr(compiler._Program, "__call__", spy)
    return seen


def _hlo(entry) -> str:
    prog, args, kwargs = entry
    return prog._fn.lower(*args, **kwargs).compile().as_text()


def _op_names(hlo: str, opcode: str) -> list:
    """``op_name`` of every instruction of ``opcode`` (fusions included
    through their root: XLA names a fusion's metadata after it)."""
    out = []
    for line in hlo.splitlines():
        if re.search(rf"\b{opcode}\(", line):
            m = re.search(r'op_name="([^"]*)"', line)
            if m:
                out.append(m[1])
    return out


def _innermost(op_name: str):
    found = _STAGE.findall(op_name)
    return found[-1] if found else None


def _tables(env, n=4096):
    rng = np.random.default_rng(7)
    left = ct.Table.from_pydict({"k": rng.integers(0, 3000, n),
                                 "a": rng.integers(0, 100, n)}, env)
    right = ct.Table.from_pydict({"k": rng.integers(0, 3000, n),
                                  "b": rng.integers(0, 100, n)}, env)
    return left, right


def test_join_groupby_route_names(env1, launched):
    from cylon_tpu.relational import groupby_aggregate, join_tables
    left, right = _tables(env1)
    out = groupby_aggregate(join_tables(left, right, "k", "k", how="inner"),
                            "k", [("a", "sum"), ("b", "sum")])
    assert out.row_count > 0
    assert {"join__count_fn", "fused__fused_fn"} <= set(launched)
    assert "per_shard" not in launched
    count = _hlo(launched["join__count_fn"])
    assert count.startswith("HloModule jit_join__count_fn")
    sorts = _op_names(count, "sort")
    assert sorts and all(_innermost(n) == "sort_keys" for n in sorts)
    assert all("jit(join__count_fn)/cylon.join/" in n for n in sorts)
    fused = _hlo(launched["fused__fused_fn"])
    assert fused.startswith("HloModule jit_fused__fused_fn")
    # `starts` is a one-operand sort since PR 33: the program's only sort
    assert _op_names(fused, "scatter") == []
    assert {_innermost(n) for n in _op_names(fused, "sort")} \
        == {"segment_starts"}
    gathers = _op_names(fused, "gather")
    assert gathers and {_innermost(n) for n in gathers} \
        == {"segment_gather"}


def test_payload_layout_helpers_are_staged(env1, launched):
    """ops/join's layout helpers at the cells' schema (ISSUE 35): the
    payload operand the two sides share is built under ``pack``, the lanes
    come back out of the sorted arrays under ``unpack`` (the aliased key's
    bitcast among them), and no row-scale equation of either program is
    left without a stage."""
    from cylon_tpu.relational import groupby_aggregate, join_tables
    left, right = _tables(env1, n=4000)          # under capacity
    groupby_aggregate(join_tables(left, right, "k", "k", how="inner"),
                      "k", [("a", "sum"), ("b", "sum")])
    traced = {}
    for builder in ("join__count_fn", "fused__fused_fn"):
        prog, args, kwargs = launched[builder]
        traced[builder] = jax.make_jaxpr(prog._fn)(*args, **kwargs)
        assert _unstaged(traced[builder]) == []
    n = left.capacity + right.capacity
    sort, = [e for e, _ in _staged_eqns(traced["join__count_fn"])
             if e.primitive.name == "sort"]
    assert len(sort.invars) == 3        # key, idx, a over b (ISSUE 50)
    shared = [st for e, st in _staged_eqns(traced["join__count_fn"])
              if e.primitive.name == "concatenate"
              and e.outvars[0] is sort.invars[2]]
    assert len(shared) == 1 and shared[0][-1] == "pack"
    casts = [st for e, st in _staged_eqns(traced["fused__fused_fn"])
             if e.primitive.name == "bitcast_convert_type"
             and e.invars[0].aval.shape == (n,)
             and str(e.invars[0].aval.dtype) == "int32"
             and str(e.outvars[0].aval.dtype) == "uint32"]
    assert casts and all(st[-1] == "unpack" for st in casts)


def test_groupby_sort_route_names(env1, launched):
    from cylon_tpu.relational import groupby_aggregate, sort_table
    left, _ = _tables(env1)
    out = sort_table(groupby_aggregate(left, "k", [("a", "sum")]), "a_sum")
    assert out.row_count > 0
    assert {"groupby__raw_fn", "sort__local_sort_fn"} <= set(launched)
    raw = _hlo(launched["groupby__raw_fn"])
    assert raw.startswith("HloModule jit_groupby__raw_fn")
    assert {_innermost(n) for n in _op_names(raw, "sort")} \
        == {"sort_keys", "segment_starts"}
    assert _op_names(raw, "scatter") == []
    assert {_innermost(n) for n in _op_names(raw, "gather")} \
        == {"segment_gather"}
    srt = _hlo(launched["sort__local_sort_fn"])
    assert srt.startswith("HloModule jit_sort__local_sort_fn")
    assert {_innermost(n) for n in _op_names(srt, "sort")} == {"sort_keys"}
    assert all("cylon.sort/" in n for n in _op_names(srt, "sort"))


def _grouped_reduce_route(builder, env1, env4):
    from cylon_tpu.relational import groupby_aggregate, join_tables
    if builder == "fused__fused_fn":
        left, right = _tables(env1)
        return groupby_aggregate(
            join_tables(left, right, "k", "k", how="inner"), "k",
            [("a", "sum"), ("b", "sum")])
    env = env1 if builder == "groupby__raw_fn" else env4
    return groupby_aggregate(_tables(env)[0], "k", [("a", "sum")])


@pytest.mark.parametrize("builder", [
    "fused__fused_fn", "groupby__raw_fn", "groupby__combine_fn",
    "groupby__final_fn"])
def test_segment_starts_is_one_unstable_one_operand_sort(builder, env1,
                                                         env4, launched):
    """Neither the scatter nor the stability ``iota`` (a second operand:
    the sort twice as long on XLA:TPU) can come back unseen."""
    assert _grouped_reduce_route(builder, env1, env4).row_count > 0
    prog, args, kwargs = launched[builder]
    traced = jax.make_jaxpr(prog._fn)(*args, **kwargs)
    under = [e for e, st in _staged_eqns(traced)
             if st and st[-1] == "segment_starts"]
    prims = [e.primitive.name for e in under]
    assert not [p for p in prims if p.startswith("scatter")], prims
    sorts = [e for e in under if e.primitive.name == "sort"]
    assert len(sorts) == 1, prims
    assert len(sorts[0].invars) == 1
    assert sorts[0].params["is_stable"] is False


def test_short_name_is_module_and_builder():
    assert short_name("cylon_tpu.relational.join._count_fn") \
        == "join__count_fn"
    assert short_name("cylon_tpu.relational.fused._fused_fn") \
        == "fused__fused_fn"
    assert short_name("f") == "f"


def test_names_do_not_change_the_program(env1):
    """A scope is metadata: the same function with and without a stage
    lowers to the same StableHLO once locations are stripped."""
    import jax.numpy as jnp

    def plain(x):
        return jnp.cumsum(x) + 1

    def scoped(x):
        with stages.stage("scan"):
            return jnp.cumsum(x) + 1

    x = jax.ShapeDtypeStruct((1024,), np.int32)
    a = jax.jit(plain).lower(x).as_text().replace("plain", "f")
    b = jax.jit(scoped).lower(x).as_text().replace("scoped", "f")
    assert a == b
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


# ---- (c) the join's liveness is a position compare (ISSUE 27) --------------

def _join_programs(mesh, cap):
    """The per-shard functions of every join program that knows row
    liveness in sorted space, with ``all_live=False``, and their abstract
    arguments at ``cap`` rows a side a shard."""
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import join as rj
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32)
    keys, valids = (S((w * cap,), np.int64),), (S((w * cap,), np.bool_),)
    cat = S((w * 2 * cap,), np.int32)
    table_args = (vc, vc, keys, valids, keys, valids)
    spec = lanes.plan_lanes(("int32", "int32"), (False, False))
    mat = S((w * 2 * cap, spec.n_lanes), np.uint32)

    from cylon_tpu.ops import join as joink
    layout = joink.payload_layout(spec, spec, (0,), ("int32",), (False,),
                                  (False,), False)

    def packed(slim):
        return rj._packed_count_fn(mesh, "left", (False,), (False,), spec,
                                   spec, layout, (0,), (0,), cap, cap, 1, 1,
                                   False, slim)

    return {
        "count_full": (rj._count_fn(mesh, "outer", (False,)),
                       table_args + ((), (), (), ())),
        "count_slim": (rj._count_fn(mesh, "inner", (False,), slim=True),
                       table_args + ((), (), (), ())),
        "carry": (rj._carry_fn(mesh, "right", cap, False),
                  (vc, vc, cat, cat)),
        "packed_count": (packed(False), (vc, vc, vc, vc, mat, mat)),
        "packed_count_slim": (packed(True), (vc, vc, vc, vc, mat, mat)),
        "semi_flag": (rj._semi_flag_fn(mesh, (False,), False, False),
                      table_args),
    }


@pytest.mark.parametrize("program", [
    "count_full", "count_slim", "carry", "packed_count",
    "packed_count_slim", "semi_flag"])
def test_join_liveness_is_not_a_gather(program, env4):
    """No join program gathers an N-length bool mask through ``idx_s``:
    live rows are the sorted prefix, liveness is ``pos < n_live``
    (ops/join.live_sides).  The gather cost 0.63 s of a 2.2 s query at
    65M rows (PERF.md, PR 27); this keeps it from coming back on a route
    no benchmark cell runs."""
    from cylon_tpu.analysis.jaxpr_check import iter_eqns
    cap = 512
    fn, args = _join_programs(env4.mesh, cap)[program]
    traced = jax.make_jaxpr(registry.unwrap(fn))(*args)
    prims = [e.primitive.name for e, _ in iter_eqns(traced)]
    assert "sort" in prims or program == "carry"     # the walk sees inside
    masks = [e for e, _ in iter_eqns(traced)
             if e.primitive.name == "gather"
             and e.invars[0].aval.dtype == np.bool_
             and e.invars[0].aval.shape == (2 * cap,)]
    assert masks == []
