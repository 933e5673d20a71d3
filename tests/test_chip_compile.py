"""Compile the main path's chip programs for a DESCRIBED TPU v5e — no chip.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described and not attached (on-chip-measurement guide, section 2):
what Mosaic / XLA:TPU refuse here they refuse on the chip, at no chip
time.  Nothing runs, so these tests say nothing about results or times.

Rules this file keeps (the driver runs the suite with several workers and
only one process may load libtpu): the topology is described ONLY inside
the module-scoped fixture below — never at import, in a skipif, in
parametrize or in conftest — and every test of it lives in this one file.
Code that asks ``jax.default_backend()`` sees the CPU here, so the tests
steer it themselves (``interpret=False``, builders called directly).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh1(topo):
    from cylon_tpu.ctx.context import ROW_AXIS
    return Mesh(np.array(topo.devices[:1]), (ROW_AXIS,))


@pytest.fixture(scope="module")
def mesh4(topo):
    """The four described chips of the v5e:2x2 host, as the engine's mesh."""
    from cylon_tpu.ctx.context import ROW_AXIS
    return Mesh(np.array(topo.devices[:4]), (ROW_AXIS,))


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# the fused join->groupby gather at bench shape: 64M concat rows of
# 7-8 u32 lanes, 2^25 group starts, every window pick_window can return
@pytest.mark.parametrize("L,M,S,window", [
    (8, 1 << 26, 1 << 25, 1024),
    (8, 1 << 26, 1 << 25, 4096),
    (7, (1 << 26) + 1, 1 << 25, 2048),
])
def test_windowed_gather_compiles_for_v5e(one_chip, L, M, S, window):
    from cylon_tpu.ops import pallas_gather as pg
    assert pg.supported(M, S, L, window)
    S_ = jax.ShapeDtypeStruct
    mat_t = S_((L, M), jnp.uint32, sharding=one_chip)
    idx = S_((S,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda m, i: pg.windowed_take_t(m, i, window,
                                                 interpret=False))
    compiled = fn.lower(mat_t, idx).compile()
    assert _has_kernel(compiled)
    out, _ok = jax.eval_shape(fn, mat_t, idx)
    assert out.shape == (L, S) and out.dtype == jnp.uint32


# the pipelined join's phase-1 probe at a 32M-row shard: few splitters of
# two operands (int64 key = hi/lo lanes) and the MAX_SPLITTERS-1 x 3 edge
@pytest.mark.parametrize("n_split,n_ops", [(5, 2), (127, 3)])
def test_probe_kernel_compiles_for_v5e(one_chip, n_split, n_ops):
    from cylon_tpu.ops import pallas_probe as pp
    cap = 1 << 25
    assert pp.supported(cap, n_split, ("i",) * n_ops)
    S_ = jax.ShapeDtypeStruct
    ops = tuple(S_((cap,), jnp.int32 if i else jnp.uint32, sharding=one_chip)
                for i in range(n_ops))
    sops = tuple(S_((n_split,), o.dtype, sharding=one_chip) for o in ops)
    fn = jax.jit(lambda o, s: pp.count_ge_splitters(o, s, interpret=False))
    compiled = fn.lower(ops, sops).compile()
    assert _has_kernel(compiled)


def _spy(monkeypatch, module, name, log):
    """Record (static args, call args) of every program a cached builder
    hands out while the path runs on the CPU rig."""
    orig = getattr(module, name)

    def builder(mesh, *static):
        fn = orig(mesh, *static)

        def call(*args):
            log.append((static, args))
            return fn(*args)
        return call
    monkeypatch.setattr(module, name, builder)
    return orig


def _abstract(args, mesh):
    """The call's arguments as shapes placed on the described mesh."""
    def one(x):
        spec = x.sharding.spec if isinstance(x, jax.Array) else P()
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(one, args)


def _capture_main_path(env, monkeypatch):
    """Run chip_smoke.py's two routes on the CPU rig at the rehearsal's
    65536 rows per side and return the builders with what they were
    called with: (fused_fn, fused_calls_of_the_resident_route, piece_fn,
    piece_calls)."""
    import cylon_tpu as ct
    from cylon_tpu.exec import GroupBySink, pipelined_join
    from cylon_tpu.relational import (fused, groupby_aggregate, join,
                                      join_tables)

    rng = np.random.default_rng(0)
    n = 65536
    mk = lambda: rng.integers(0, int(n * 0.9), n).astype(np.int64)  # noqa: E731
    lt = ct.Table.from_pydict({"k": mk(), "a": mk()}, env)
    rt = ct.Table.from_pydict({"k": mk(), "b": mk()}, env)
    aggs = [("a", "sum"), ("b", "sum")]
    fused_calls, piece_calls = [], []
    fused_fn = _spy(monkeypatch, fused, "_fused_fn", fused_calls)
    piece_fn = _spy(monkeypatch, join, "_packed_count_fn", piece_calls)
    groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"), "k",
                      aggs).to_pandas()
    resident = list(fused_calls)
    sink = GroupBySink("k", aggs)
    pipelined_join(lt, rt, "k", "k", how="inner", n_chunks=4, sink=sink)
    sink.finalize().to_pandas()
    assert resident and piece_calls
    monkeypatch.undo()
    return fused_fn, resident, piece_fn, piece_calls


# Rows in the two tests below are the rehearsal's, not the chip's 32M:
# XLA:TPU's compile time for these programs grows with the row count (the
# fused program: 13 s at 2^16 rows per side, two minutes at 2^25), and
# what Mosaic refuses it refuses at any size.

def test_fused_join_groupby_compiles_for_v5e(mesh1, env1, monkeypatch):
    """The resident route's whole-shard program — the fused join->groupby
    with the windowed Pallas gather inside (w>0) — lowered on a one-device
    described mesh with the lane specs and static arguments the real path
    chose, at its settled segment bucket."""
    from cylon_tpu.exec import compiler
    fused_fn, resident, _, _ = _capture_main_path(env1, monkeypatch)
    static, args = resident[-1]
    assert len(static) == 12        # ..., seg_cap@8, ddof, w, sum_forms
    seg_cap = static[8]
    assert seg_cap % 256 == 0 and seg_cap > 512, seg_cap
    assert [str(f) for f in static[11]] == ["val32/128"] * 2  # under 2^24
    prog = fused_fn(mesh1, *static[:10], 1024, *static[11:])
    # steer the kernel off interpret mode: the builder asks the backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = compiler.aot_compile(prog, *_abstract(args, mesh1))
    assert _has_kernel(compiled)


@pytest.mark.slow   # XLA:TPU takes ~80 s over this sort-heavy program
def test_packed_piece_join_compiles_for_v5e(mesh1, env1, monkeypatch):
    """One packed per-piece join program, as the range pipeline
    dispatched it."""
    from cylon_tpu.exec import compiler
    _, _, piece_fn, piece_calls = _capture_main_path(env1, monkeypatch)
    static, args = piece_calls[0]
    compiler.aot_compile(piece_fn(mesh1, *static), *_abstract(args, mesh1))


# ---- four chips (ISSUE 28) --------------------------------------------------
# The distributed join->groupby's per-shard programs on the described 2x2
# mesh at the benchmark cell's size: 8,912,896 rows per side per shard
# (17 * 2^19, the receive capacity of a 2^23-row shuffle: a capacity of
# config.pow2ceil's family that is no power of two), 17,825,792 concat rows.
# XLA:TPU's scan rewriter dies with SIGSEGV, in-process and within a second
# of starting, on a fused program for four devices that holds about four
# LONG 64-bit scans (described compiles, PR 28; PERF.md): the parent
# (efc7d6d: two int64 sums + the two counts widened to int64) at 512 slots
# with the plain gather and with the windowed one alike; with the counts as
# int32 scans and nothing else changed, two and three int64 sums compile
# and four sums, or a mean beside a var, die again.  A death kills the test
# process, so only this tree's programs (ops/groupby.blocked_cumsum on a
# mesh of more than one device) are compiled here.

_ROWS4 = 17 << 19


def _fused_args(mesh, n_side: int, layout):
    """vcl, vcr, idx_s, bnd and ``pl_s`` as ``layout`` lays it out: the
    kept sorted key (int32: a narrow key's operand), then the payload
    operands the two sides share."""
    from cylon_tpu.ctx.context import ROW_AXIS
    w = int(mesh.devices.size)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32, sharding=rep)
    idx = S((w * 2 * n_side,), np.int32, sharding=row)
    lane = S((w * 2 * n_side,), np.uint32, sharding=row)
    return vc, vc, idx, idx, (idx,) * len(layout.kept_keys) \
        + (lane,) * layout.n_payloads


def _join_specs(n_sums: int = 2):
    """Lane specs and payload layout of the benchmark's join, all int64
    within int32 bounds, tables under capacity: left (k, a), right (b) -
    or, for four sums, left (k, a, c), right (b, d).  The key's lane is
    the sorted key operand; the others share one operand a pair."""
    from cylon_tpu.ops import join as joink, lanes
    nl = n_sums // 2
    lspec = lanes.plan_lanes(("int64",) * (1 + nl), (False,) * (1 + nl),
                             (True,) * (1 + nl))
    rspec = lanes.plan_lanes(("int64",) * nl, (False,) * nl, (True,) * nl)
    layout = joink.payload_layout(lspec, rspec, (0,), ("int64",), (False,),
                                  (True,), False)
    assert layout.sort_operands == 3 + nl and layout.n_arrays == 1 + nl
    return lspec, rspec, layout


def _fused_static(n_sums: int = 2):
    """:func:`_join_specs` and the aggregations of the benchmark's query:
    sum(a), sum(b) by k (or the four sums)."""
    nl = n_sums // 2
    vspecs = tuple(("l", 1 + i, "sum") for i in range(nl)) \
        + tuple(("r", i, "sum") for i in range(nl))
    return _join_specs(n_sums) + (vspecs, (0,), (True,))


def _scan(shown: str):
    """``ops/groupby.SumScan`` from the way it prints: ``val32/64``."""
    from cylon_tpu.ops.groupby import SumScan
    word, _, block = shown.partition("/")
    return SumScan(word, int(block or 1))


def _wide_scans(compiled) -> list:
    """The ``reduce-window`` instructions of the optimised text that scan
    an (hi, lo) PAIR - what a 64-bit ``cumsum`` is on XLA:TPU."""
    import re
    return re.findall(r"(?m)^.* = \(.+\) reduce-window\(", compiled.as_text())


@pytest.mark.parametrize("n_sums,form", [(2, "pair64"), (4, "pair64"),
                                         (2, "val32/128")])
def test_first_sight_compiles_for_four_chips(mesh4, n_sums, form):
    """The first dispatch of a fused callsite: 512 segment slots, always
    XLA's gather (relational/groupby._FIRST_SEG_CAP).  Every four-chip run
    meets this program first - with the sums scanned as the cells' bounded
    columns are (``val32`` in blocks of 128, their values being under
    2^24: no 64-bit scan in the program at all) and as an unbounded
    column's (``pair64``, in blocks)."""
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import fused
    static = _fused_static(n_sums)
    prog = fused._fused_fn(mesh4, _ROWS4, False, *static, 512, 1,
                           sum_forms=(_scan(form),) * n_sums)
    compiled = compiler.aot_compile(
        prog, *_fused_args(mesh4, _ROWS4, static[2]))
    assert not _has_kernel(compiled)
    assert bool(_wide_scans(compiled)) == (form == "pair64")


@pytest.mark.parametrize("window,n_sums,form", [
    (4096, 2, "val32/128"), (0, 2, "val32/128"), (4096, 4, "val32"),
    (4096, 4, "pair64")])
def test_fused_compiles_for_four_chips(mesh4, monkeypatch, window, n_sums,
                                       form):
    """The settled dispatch at segment space 3,407,872 (density 0.2): with
    the windowed Pallas gather inside, as an eligible callsite runs it
    (the cell's two sums, and four), and with XLA's gather, as one below
    the density floor does.  The cells' sums are ``val32`` (ISSUE 40):
    32-bit scans - in blocks of 128, or flat as a column that uses int32's
    width gets them - and no (hi, lo) pair scan left for PR 28's rewriter
    fault to meet; four ``pair64`` sums are what the fault was found on
    and stay in blocks."""
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    static = _fused_static(n_sums)
    prog = fused._fused_fn(mesh4, _ROWS4, False, *static, 3407872, 1, window,
                           sum_forms=(_scan(form),) * n_sums)
    compiled = compiler.aot_compile(
        prog, *_fused_args(mesh4, _ROWS4, static[2]))
    assert _has_kernel(compiled) == bool(window)
    assert bool(_wide_scans(compiled)) == (form == "pair64")


@pytest.mark.parametrize("world,cap", [(1, 1 << 16), (4, _ROWS4)])
def test_join_count_compiles_with_one_sort_of_four(topo, world, cap):
    """``join__count_fn`` (slim: the deferred join's) in the shared-operand
    layout (ISSUE 35) at the benchmark's schema, on one described chip at
    the rehearsal's rows and on four at the cell's 8,912,896 a side: the
    optimised text holds ONE sort, of the layout's 4 operands - liveness,
    key, ``idx``, the operand ``a`` and ``b`` share - so the stable sort's
    expansion added no tie-break ``iota`` of its own (``idx`` is one)."""
    import re
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import join
    mesh = Mesh(np.array(topo.devices[:world]), (ROW_AXIS,))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    lspec, rspec, layout = _join_specs(2)
    vc = S((world,), np.int32, sharding=rep)
    col = S((world * cap,), np.int64, sharding=row)
    prog = join._count_fn(mesh, "inner", (True,), lspec, rspec, layout,
                          False, True)
    text = compiler.aot_compile(
        prog, vc, vc, (col,), (None,), (col,), (None,), (col, col),
        (None, None), (col,), (None,)).as_text()
    sorts = re.findall(r"^.* = (.*?) sort\(", text, re.M)
    assert len(sorts) == 1, sorts
    results = re.findall(r"[su]32\[\d+\]", sorts[0])
    assert results == ["s32[%d]" % (2 * cap)] * 3 + ["u32[%d]" % (2 * cap)]
    assert len(results) == layout.sort_operands == 4


def _hlo_ops(text: str, opcode: str) -> int:
    """Instructions of one opcode in compiled HLO text (async pairs count
    once, by their ``-start``)."""
    import re
    return len(re.findall(rf"= \S+ {opcode}(?:-start)?\(", text))


@pytest.mark.parametrize("cap,lanes,block,out_cap,rounds", [
    (1 << 23, 2, 17 << 17, 17 << 19, 1),   # dist_join_groupby_8m_x4's shapes
    (1 << 21, 4, 17 << 13, 17 << 17, 3),   # groupby_sort_25m_x4's four lanes
    (1 << 21, 2, 17 << 15, 17 << 17, 1),
])
def test_shuffle_round_compiles_for_four_chips(mesh4, cap, lanes, block,
                                               out_cap, rounds):
    """One table's exchange, one u32 lane matrix: ``_prep_fn`` with the
    lanes riding its sort by target — ONE sort of ``1 + lanes`` operands,
    the (target, position) key in one word, so the sort is not stable and
    XLA:TPU adds no tie-break operand of its own — then ``_round_fn``: the
    send blocks as slices of the target-sorted rows, the all_to_all, each
    received block's valid prefix copied to its final place (``out_cap``
    the next capacity of config.pow2ceil's family).  Neither program holds
    a gather of rows or a scatter, and the rounds exactly the one
    all_to_all."""
    import re
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.exec import compiler
    from cylon_tpu.parallel import shuffle
    w = 4
    rep, row = NamedSharding(mesh4, P()), NamedSharding(mesh4, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    mat = S((w * cap, lanes), np.uint32, sharding=row)
    assert shuffle.ride_rule((mat,)) == (1 + lanes, None)
    prep = compiler.aot_compile(
        shuffle._prep_fn(mesh4, w, True),
        S((w * cap,), np.int32, sharding=row), (mat,)).as_text()
    sorts = re.findall(r"^.* = (.*?) sort\(", prep, re.M)
    assert len(sorts) == 1, sorts
    assert re.findall(r"[su]32\[\d+\]", sorts[0]) \
        == ["u32[%d]" % cap] * (1 + lanes)
    text = compiler.aot_compile(
        shuffle._round_fn(mesh4, w, block, out_cap, rounds),
        S((w, w), np.int32, sharding=rep),
        (S((w * out_cap, lanes), np.uint32, sharding=row),), (mat,)).as_text()
    assert _hlo_ops(text, "all-to-all") == 1
    for program in (prep, text):
        # the count matrix's row and column for the members are picked by
        # two gathers of ``w`` numbers; no gather of rows
        assert set(re.findall(r"= (\S+?)\{\S* gather\(", program)) \
            <= {"s32[%d]" % w}
        assert "scatter" not in program


def test_shuffle_count_compiles_for_four_chips(mesh4):
    """The count sidecar at the cell's shard size: a dense
    compare-and-reduce, no scatter-add."""
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.exec import compiler
    from cylon_tpu.parallel import shuffle
    row = NamedSharding(mesh4, P(ROW_AXIS))
    text = compiler.aot_compile(
        shuffle._count_fn(mesh4, 4),
        jax.ShapeDtypeStruct((4 << 23,), np.int32, sharding=row)).as_text()
    assert "scatter" not in text


# ---- the standalone groupby with the window (ISSUE 31) ----------------------
# relational/groupby's two dispatch sites ask for the windowed gather under
# the fused path's rule; these are their programs as an eligible callsite
# runs them: one int64 sum by a narrow int64 key (benchmark cell
# groupby_sort_25m's query: 3 u32 lanes, padded to 8 for the kernel).

def _groupby_args(mesh, cap: int):
    from cylon_tpu.ctx.context import ROW_AXIS
    w = int(mesh.devices.size)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    col = S((w * cap,), np.int64, sharding=row)
    return S((w,), np.int32, sharding=rep), (col,), (None,), (col,), (None,)


def _groupby_program(mesh, site: str, seg_cap: int, window: int,
                     form: str = "val32/64"):
    """``form``: how the sum is scanned; ``val32`` in blocks of 64 is what
    the cell's bounded column gets (relational/groupby.sum_scan_form)."""
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import groupby as rel_gb
    vspec = lanes.plan_lanes(("int64", "int64"), (False, False), (True, True))
    if site == "combine":
        return rel_gb._combine_fn(mesh, ("sum",), seg_cap, False, (True,),
                                  (_scan(form),), vspec, (0,), window)
    return rel_gb._raw_fn(mesh, (("sum", 0.5),), seg_cap, 1, False, (True,),
                          (_scan(form),), vspec, (0,), window)


@pytest.mark.parametrize("cap,seg_cap", [
    (69632, 40960),
    # groupby_sort_25m's own shapes: 25M rows, ~15.09M groups (about a
    # minute of XLA:TPU, most of it the 4-operand sort)
    (25165824, 15204352),
])
def test_windowed_raw_groupby_compiles_for_v5e(mesh1, monkeypatch, cap,
                                               seg_cap):
    """``groupby__raw_fn`` at its settled segment bucket on one described
    chip, with the windowed Pallas gather inside (window 1024: what
    ``pick_window`` gives the cell's density 0.60)."""
    from cylon_tpu.exec import compiler
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = compiler.aot_compile(
        _groupby_program(mesh1, "raw", seg_cap, 1024),
        *_groupby_args(mesh1, cap))
    assert _has_kernel(compiled)
    assert not _wide_scans(compiled)


@pytest.mark.parametrize("site,form", [("combine", "val32/64"),
                                       ("raw", "val32"), ("raw", "pair64")])
def test_windowed_groupby_compiles_for_four_chips(mesh4, monkeypatch, site,
                                                  form):
    """Phase 1 of the distributed associative groupby and the raw route on
    a mesh of four, with the kernel inside: forms no chip has run yet."""
    from cylon_tpu.exec import compiler
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = compiler.aot_compile(
        _groupby_program(mesh4, site, 16384, 1024, form),
        *_groupby_args(mesh4, 17408))
    assert _has_kernel(compiled)
    assert bool(_wide_scans(compiled)) == (form == "pair64")


# ---- the filter (ISSUE 43) --------------------------------------------------
# Q3's ``lineitem`` filter at the TPC-H cell's size: a 30,408,704-row shard,
# 16,252,928 output slots, 15 columns in 18 lanes (12 narrow int64 / code
# columns, 3 two-lane dates) - 24 rows after the pad to a sublane multiple,
# three times the widest stack the grouped reduce gives the kernel.  XLA:TPU
# compiles these in seconds (a one-operand sort, no wide sort).

def _filter_programs(mesh, cap, out_cap, window, n_narrow=12, n_wide=3):
    from cylon_tpu.analysis.registry import unwrap
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import repart
    w = int(mesh.devices.size)
    n = n_narrow + n_wide
    spec = lanes.plan_lanes(("int64",) * n, (False,) * n,
                            (True,) * n_narrow + (False,) * n_wide)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32, sharding=rep)
    count = jax.jit(unwrap(repart._filter_count_fn(mesh, cap))).lower(
        vc, S((w * cap,), np.bool_, sharding=row)).compile()
    cols = tuple(S((w * cap,), np.int64, sharding=row) for _ in range(n))
    mat = jax.jit(unwrap(repart._filter_mat_fn(
        mesh, cap, out_cap, spec, window))).lower(
            vc, S((w * cap,), np.int32, sharding=row), cols,
            (None,) * n).compile()
    return spec, count, mat


@pytest.mark.parametrize("world,cap,out_cap,window", [
    (1, 30408704, 16252928, 1024), (1, 7602176, 1179648, 4096),
    (1, 30408704, 16252928, 0), (4, 1 << 21, 1 << 20, 2048)])
def test_filter_programs_compile_for_v5e(topo, monkeypatch, world, cap,
                                         out_cap, window):
    """``repart__filter_count_fn`` (ONE one-operand sort, no scatter) and
    ``repart__filter_mat_fn`` with the windowed take inside at the lane
    width of Q3's ``lineitem`` - and with XLA's gather, whose program holds
    no scatter and no sort either since the index comes sorted."""
    import re
    from cylon_tpu.ctx.context import ROW_AXIS
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:world]), (ROW_AXIS,))
    spec, count, mat = _filter_programs(mesh, cap, out_cap, window)
    assert spec.n_lanes == 18
    text = count.as_text()
    sorts = re.findall(r"(?m)^.* = (\S+) sort\(", text)
    assert len(sorts) == 1 and sorts[0].startswith("s32["), sorts
    assert " scatter(" not in text
    assert _has_kernel(mat) == bool(window)
    assert " sort(" not in mat.as_text() and " scatter(" not in mat.as_text()


# ---- the distributed groupby -> sort (ISSUE 44) ------------------------------
# What benchmark cell groupby_sort_25m_x4 launches on a mesh of four beside
# phase 1 (``_combine_fn``, above): phase 2 of the two-phase groupby, whose
# sums scan as ``pair64`` - partial sums have no bounds - in blocks of 128
# (PR 28: XLA:TPU's scan rewriter dies on long 64-bit scans of a
# multi-device program), and the sample sort's three builders with a
# two-operand int64 key.  A small shard: what the compiler refuses it
# refuses at any size, and its time grows with the rows.

_GS_SHARD = 17408
#: the cell's own phase 2 (ISSUE 45): a shard is the hash exchange's receive
#: capacity, the segment space the bucket of its ~15.09M groups a chip -
#: density 0.69, so ``pick_window`` gives 1024
_GS_CELL_SHARD, _GS_CELL_SEG = 22020096, 15204352


def _dist_sort_program(mesh, which: str, cap: int):
    """``(program, abstract args)`` of one builder of the cell's query: an
    int64 ``sum`` by a narrow int64 key, then a sort by the sum."""
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.ops import lanes, pack
    from cylon_tpu.relational import groupby as rel_gb, sort as rel_sort
    w = int(mesh.devices.size)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32, sharding=rep)
    col = S((w * cap,), np.int64, sharding=row)
    if which == "final":
        return (rel_gb._final_fn(mesh, ("sum",), cap, 1, (True,)),
                (vc, (col,), (None,), ((col,),)))
    if which == "final_windowed":
        return (rel_gb._final_fn(mesh, ("sum",), _GS_CELL_SEG, 1, (True,),
                                 1024), (vc, (col,), (None,), ((col,),)))
    desc, npos, narrow = (False,), pack.NULL_LAST, (False,)
    if which == "sample":
        return (rel_sort._sample_fn(mesh, 64, desc, npos, narrow),
                (vc, (col,), (None,)))
    if which == "target":
        splitters = (S((w - 1,), np.int32, sharding=rep),
                     S((w - 1,), np.uint32, sharding=rep))
        return (rel_sort._target_fn(mesh, desc, npos, narrow),
                (vc, (col,), (None,), splitters))
    # the result of phase 2: the key and the sum, both wide by then
    vspec = lanes.plan_lanes(("int64", "int64"), (False, False),
                             (False, False))
    return (rel_sort._local_sort_fn(mesh, desc, npos, narrow, vspec, (),
                                    (1,), False),
            (vc, (col, col), (None, None)))


@pytest.mark.parametrize("which", ["final", "final_windowed", "sample",
                                   "target", "local_sort"])
def test_dist_groupby_sort_compiles_for_four_chips(mesh4, monkeypatch, which):
    """``groupby__final_fn``, ``sort__sample_fn``, ``sort__target_fn`` and
    ``sort__local_sort_fn`` for four described chips.  Phase 2's 64-bit
    scans are all in blocks: no (hi, lo) pair ``reduce-window`` runs the
    length of a shard, the form the rewriter dies on.  ``final_windowed``:
    phase 2 as ``dispatch_at_bucket`` settles it in the cell, at the cell's
    own shapes - the segment space at the groups' bucket, the windowed
    Pallas take inside (about a minute of XLA:TPU)."""
    import re
    from cylon_tpu.exec import compiler
    from cylon_tpu.ops import groupby as gbk
    windowed = which == "final_windowed"
    shard = _GS_CELL_SHARD if windowed else _GS_SHARD
    if windowed:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    program, args = _dist_sort_program(mesh4, which, shard)
    compiled = compiler.aot_compile(program, *args)
    text = compiled.as_text()
    assert _has_kernel(compiled) == windowed
    sorts = re.findall(r"(?m)^.* = (.+) sort\(", text)
    if which.startswith("final"):
        wide = _wide_scans(compiled)
        assert wide                       # pair64: the sums ARE 64-bit scans
        for line in wide:
            shapes = re.findall(r"[su]32\[([\d,]+)\]", line.split(
                " reduce-window(")[0])
            assert shapes and all(
                max(int(d) for d in s.split(",")) * gbk._SCAN_BLOCK
                <= 2 * shard for s in shapes), line
    elif which == "local_sort":
        # ONE sort of eight operands, the one-chip cell's: liveness, the
        # key's (hi, lo), four payload lanes, XLA's own index for stability
        assert len(sorts) == 1 and sorts[0].count("[") == 8, sorts
    else:
        assert not sorts and " all-to-all(" not in text
