"""One described chip: the two Pallas kernels at bench widths, the resident
route's fused program, ``groupby__raw_fn`` with the window, the filter's
two programs, ``unique``'s two (the set operations' four are
``test_setops.py``: the rules, this package's docstring)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .helpers import (_check_setop_programs, _groupby_args,
                      _groupby_program, _has_kernel, _wide_scans)


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


@pytest.mark.parametrize("cap,seg_cap", [
    (69632, 40960),
    # groupby_sort_25m's own shapes: 25M rows, ~15.09M groups (about a
    # minute of XLA:TPU, most of it the 3-operand sort)
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


# ---- the filter (ISSUE 43) --------------------------------------------------
# Q3's ``lineitem`` filter at the TPC-H cell's size: a 30,408,704-row shard,
# 16,252,928 output slots, 15 columns in 18 lanes (12 narrow int64 / code
# columns, 3 two-lane dates) - 24 rows after the pad to a sublane multiple,
# three times the widest stack the grouped reduce gives the kernel.  XLA:TPU
# compiles these in seconds (a one-operand sort, no wide sort).

@pytest.fixture(scope="module")
def filter_counts():
    """``repart__filter_count_fn`` compiled once a (world, shard): the cases
    at the cell's shard differ in the SECOND program's window alone."""
    return {}


def _filter_programs(mesh, cap, out_cap, window, counts, n_narrow=12,
                     n_wide=3):
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
    if (w, cap) not in counts:
        counts[w, cap] = jax.jit(unwrap(repart._filter_count_fn(
            mesh, cap))).lower(
                vc, S((w * cap,), np.bool_, sharding=row)).compile()
    count = counts[w, cap]
    cols = tuple(S((w * cap,), np.int64, sharding=row) for _ in range(n))
    mat = jax.jit(unwrap(repart._filter_mat_fn(
        mesh, cap, out_cap, spec, window))).lower(
            vc, S((w * cap,), np.int32, sharding=row), cols,
            (None,) * n).compile()
    return spec, count, mat


@pytest.mark.parametrize("world,cap,out_cap,window", [
    (1, 30408704, 16252928, 1024), (1, 7602176, 1179648, 4096),
    (1, 30408704, 16252928, 0), (4, 1 << 21, 1 << 20, 2048)])
def test_filter_programs_compile_for_v5e(topo, monkeypatch, filter_counts,
                                         world, cap, out_cap, window):
    """``repart__filter_count_fn`` (ONE one-operand sort, no scatter) and
    ``repart__filter_mat_fn`` with the windowed take inside at the lane
    width of Q3's ``lineitem`` - and with XLA's gather, whose program holds
    no scatter and no sort either since the index comes sorted."""
    import re
    from cylon_tpu.ctx.context import ROW_AXIS
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:world]), (ROW_AXIS,))
    spec, count, mat = _filter_programs(mesh, cap, out_cap, window,
                                        filter_counts)
    assert spec.n_lanes == 18
    text = count.as_text()
    sorts = re.findall(r"(?m)^.* = (\S+) sort\(", text)
    assert len(sorts) == 1 and sorts[0].startswith("s32["), sorts
    assert " scatter(" not in text
    assert _has_kernel(mat) == bool(window)
    assert " sort(" not in mat.as_text() and " scatter(" not in mat.as_text()


# ---- drop_duplicates (ISSUE 49) ---------------------------------------------
# benchmark cell setops_dedup_32m's ``unique``: the filter's pair of programs
# behind a 2-operand rank sort - ``k`` with padding's sentinel inside it, the
# row index (helpers._setop_programs).

def test_unique_programs_compile_for_v5e(mesh1, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _check_setop_programs(mesh1, "unique", 2)
