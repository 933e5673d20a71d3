"""``filter_table``'s two paths (relational/repart.py, PR 43): a filter's
kept rows are monotone in the source, so where ``filter_window`` says the
window serves they move by the windowed Pallas take (``ops/pallas_gather``),
elsewhere by XLA's gather - at ONE sorted take index that the count program
makes.  CPU rig: the kernel in interpret mode, the rule's platform and size
tests lifted by ``monkeypatch`` (as tests/test_pallas_gather.py lifts them
for the grouped reduce); a time is never taken here."""

from functools import partial

import jax
import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import obs
from cylon_tpu.core.column import Column
from cylon_tpu.obs import metrics, trace
from cylon_tpu.ops import pallas_gather as pg
from cylon_tpu.relational import fused, repart

N = 40000          # world 1: capacity 40960; world 4: 10000 a shard, 16384


def _frame(env, laneless_only=False):
    """Every kind of column a lane matrix carries: a two-lane int64, a
    narrow one (bounds fit int32: ONE lane), a nullable int64, DECIMAL, a
    dictionary string, a nullable float64 (laneless: a side gather, its
    validity bit rides a lane) - and ``pos`` / ``u`` to build flags from."""
    rng = np.random.default_rng(7)
    f = rng.random(N)
    if laneless_only:
        return ct.DataFrame({"u": rng.random(N), "f": f}, env=env)
    words = np.array(["BUILDING", "AUTOMOBILE", "MACHINERY", "FURNITURE"])
    return ct.DataFrame({
        "pos": np.arange(N, dtype=np.int64),
        "u": rng.random(N),
        "wide": rng.integers(-(1 << 50), 1 << 50, N),
        "narrow": rng.integers(-1000, 1000, N).astype(np.int64),
        "nullable": pd.array(np.where(rng.random(N) < 0.2, None,
                                      rng.integers(0, 99, N)), dtype="Int64"),
        "money": Column.from_scaled_ints(rng.integers(90000, 10500000, N),
                                         2, 15),
        "seg": Column.from_dictionary(rng.integers(0, 4, N), words),
        "f": pd.array(np.where(rng.random(N) < 0.1, None, f),
                      dtype="Float64"),
    }, env=env)


#: case -> (flag on the frame, expected path under the lifted rule, reason)
CASES = {
    "density_0.9": (lambda d: d["u"] < 0.9, "windowed", ""),
    "density_0.5": (lambda d: d["u"] < 0.5, "windowed", ""),
    "density_0.12": (lambda d: d["u"] < 0.12, "windowed", ""),
    # the sentinel case: nothing of the table's tail is kept, so a fill
    # behind the table would widen the last real tile past any window
    "first_half": (lambda d: d["pos"] < N // 2, "windowed", ""),
    "last_row": (lambda d: d["pos"] == N - 1, "plain",
                 "density_below_floor"),
    "nothing": (lambda d: d["pos"] < 0, "plain", "density_below_floor"),
    "everything": (lambda d: d["pos"] >= 0, "windowed", ""),
    # locally sparse: one tile's kept rows span 6000 source rows
    "span_overflow": (lambda d: (d["pos"] < 200) | (d["pos"] > 6200),
                      "plain", "span_overflow"),
    "density_0.05": (lambda d: d["u"] < 0.05, "plain",
                     "density_below_floor"),
    "laneless_only": (lambda d: d["u"] < 0.5, "plain", "laneless_only"),
}


def _lifted_rule(mesh, out_cap, density):
    """``fused.window_rule`` without its platform and size tests."""
    if density < pg.MIN_DENSITY:
        return 0, "density_below_floor"
    return pg.pick_window(density), ""


def _dispatches():
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("filter_dispatches")}


def _run(frame, flag_of):
    """The filter alone (its flag is made first): result, its plan node,
    the pulls and launches of the one call."""
    flag = flag_of(frame)
    rec = trace.arm(capacity=256)
    try:
        qp = obs.explain_analyze(lambda: frame[flag])
        names = [e[3] for e in rec.events()]
    finally:
        trace.disarm()
    (node,) = [r for r in qp.roots if r.op == "filter"]
    return qp.result, node, names


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("case", list(CASES))
def test_filter_paths_agree(case, world, env1, env4, monkeypatch):
    flag_of, path, reason = CASES[case]
    frame = _frame(env1 if world == 1 else env4, case == "laneless_only")
    base = frame.to_pandas()
    mask = np.asarray(flag_of(frame).to_pandas(), bool)
    expected = base[mask].reset_index(drop=True)

    # as the CPU rig runs it: XLA's gather, the rule's own word for it
    before = _dispatches()
    plain, node, names = _run(frame, flag_of)
    assert node.attrs["path"] == "plain" and node.attrs["window"] == 0
    assert _dispatches()['filter_dispatches{path="plain",reason="not_tpu"}'] \
        == before['filter_dispatches{path="plain",reason="not_tpu"}'] + 1
    assert sum(n.startswith("pull.") for n in names) == 1
    got_plain = plain.to_pandas()
    # (an empty string column comes back as object: dtypes where rows are)
    pd.testing.assert_frame_equal(got_plain, expected,
                                  check_dtype=bool(mask.any()))

    # the rule lifted: the kernel (interpret mode) wherever the data allow
    monkeypatch.setattr(fused, "window_rule", _lifted_rule)
    monkeypatch.setattr(repart, "shard_map",
                        partial(jax.shard_map, check_vma=False))
    built = []         # the materialize builder's statics, a dispatch each
    builder = repart._filter_mat_fn
    monkeypatch.setattr(repart, "_filter_mat_fn", lambda mesh, *static: (
        built.append(static), builder(mesh, *static))[1])
    before = _dispatches()
    win, node, names = _run(frame, flag_of)
    key = 'filter_dispatches{path="windowed"}' if path == "windowed" else \
        f'filter_dispatches{{path="plain",reason="{reason}"}}'
    after = _dispatches()
    assert {k: after[k] - before[k] for k in after if after[k] != before[k]} \
        == {key: 1}
    assert node.attrs["path"] == path
    assert node.rows_out == int(mask.sum()) and node.rows_in == N
    # the same pull, the same two programs: the span came with the count
    assert sum(n.startswith("pull.") for n in names) == 1
    assert [n for n in names if n.startswith("launch.repart__filter")] == [
        "launch.repart__filter_count_fn", "launch.repart__filter_mat_fn"]
    if path == "windowed":
        window = node.attrs["window"]
        assert window == pg.pick_window(node.attrs["density"])
        assert 0 < node.attrs["max_tile_span"] <= window
        assert [b[-1] for b in built] == [window]     # the kernel's program
    else:
        assert node.attrs["window"] == 0 and [b[-1] for b in built] == [0]
        if reason == "span_overflow":
            assert node.attrs["max_tile_span"] > pg.pick_window(
                node.attrs["density"])
    # the same rows in the same order, bit for bit, and the same bounds
    pd.testing.assert_frame_equal(win.to_pandas(), got_plain)
    np.testing.assert_array_equal(win.table.valid_counts,
                                  plain.table.valid_counts)
    for name, col in frame.table.columns.items():
        out = win.table.columns[name]
        assert out.type == col.type and out.bounds == (
            None if col.bounds is None
            else (min(col.bounds[0], 0), max(col.bounds[1], 0)))
        assert out.bounds == plain.table.columns[name].bounds


@pytest.mark.parametrize("keep", ["head", "tail", "gap", "none"])
def test_max_tile_span_is_the_kernels_own_test(keep):
    """``max_tile_span`` read before a row is moved says what
    ``windowed_take_t``'s ``ok`` says after: a window serves iff it holds
    the widest tile, the padding riding at the last kept position."""
    n, out_cap = 8192, 2048
    pos = np.arange(n)
    flag = {"head": pos < 1500, "tail": pos >= n - 1500,
            "gap": (pos < 300) | (pos > 5000), "none": pos < 0}[keep]
    n_kept = int(flag.sum())
    srt = np.sort(np.where(flag, pos, n)).astype(np.int32)
    last = int(srt[n_kept - 1]) if n_kept else 0
    span = int(pg.max_tile_span(jax.numpy.asarray(srt), last))
    idx = np.minimum(srt[:out_cap], last)
    mat = jax.numpy.asarray(np.random.default_rng(0).integers(
        0, 1 << 32, (8, n), dtype=np.uint32))
    for window in (1024, 2048, 4096):
        out, ok = pg.windowed_take_t(mat, jax.numpy.asarray(idx), window,
                                     interpret=True)
        assert bool(ok) == (span <= window)
        if span <= window:
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(mat)[:, idx])
    assert (span > 4096) == (keep == "gap")


def test_filter_window_reads_the_rule_as_it_stands(env1):
    """No second copy of the thresholds: ``filter_window`` asks
    ``fused.window_rule`` (on the CPU its first word), then the shape and
    the measured span."""
    mesh = env1.mesh
    assert repart.filter_window(mesh, 1 << 21, 1 << 20, 18, 0.5, 600) \
        == (0, "not_tpu")
    rule = fused.window_rule
    try:
        fused.window_rule = _lifted_rule
        assert repart.filter_window(mesh, 1 << 21, 1 << 20, 18, 0.5, 600) \
            == (1024, "")
        assert repart.filter_window(mesh, 1 << 21, 1 << 20, 18, 0.5, 1025) \
            == (0, "span_overflow")
        assert repart.filter_window(mesh, 1 << 21, 1 << 20, 0, 0.5, 600) \
            == (0, "laneless_only")
        assert repart.filter_window(mesh, (1 << 21) + 64, 1 << 20, 18, 0.5,
                                    600) == (0, "unsupported_shape")
        assert repart.filter_window(mesh, 1 << 21, 1 << 20, 18, 0.01, 600) \
            == (0, "density_below_floor")
    finally:
        fused.window_rule = rule
