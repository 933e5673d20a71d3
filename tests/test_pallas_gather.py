"""Windowed Pallas gather (ops/pallas_gather) — interpret-mode checks on
the CPU rig; the real-TPU path is exercised by the benchmark's cells and
compiled for a described chip by tests/chip_compile/."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cylon_tpu.ops import pallas_gather as pg


def _ref(mat, idx):
    return np.asarray(mat)[:, np.asarray(idx)]


def _mk(n_rows, n_lanes, seg, density_pattern, rng):
    # lane-major (L, M), as the API requires
    mat = jnp.asarray(
        rng.integers(0, 1 << 32, (n_lanes, n_rows), dtype=np.uint32))
    if density_pattern == "dense":
        k = min(int(n_rows * 0.45), seg)
        real = np.sort(rng.choice(n_rows - 1, k, replace=False))
    elif density_pattern == "skewed":
        # one huge group: a long index gap that overflows any window
        k = min(int(n_rows * 0.45), seg)
        real = np.sort(rng.choice(n_rows // 8, k - 1, replace=False))
        real = np.concatenate([real, [n_rows - 1]])
    else:  # tail sentinels only
        real = np.zeros(0, np.int64)
    idx = np.full(seg, n_rows - 1, np.int32)
    idx[:len(real)] = real
    return mat, jnp.asarray(idx)


class TestWindowedTake:
    @pytest.mark.parametrize("n_lanes", [1, 7, 8, 13])
    def test_matches_plain_gather(self, rng, n_lanes):
        n_rows, seg = 4096, 2048
        mat, idx = _mk(n_rows, n_lanes, seg, "dense", rng)
        out, ok = jax.jit(lambda m, i: pg.windowed_take_t(
            m, i, window=1024, interpret=True))(mat, idx)
        assert bool(np.asarray(ok))
        np.testing.assert_array_equal(np.asarray(out), _ref(mat, idx))

    def test_sentinel_tail(self, rng):
        # all-sentinel tail tiles (empty groups past n_groups)
        mat, idx = _mk(4096, 5, 1024, "tail", rng)
        out, ok = jax.jit(lambda m, i: pg.windowed_take_t(
            m, i, window=1024, interpret=True))(mat, idx)
        assert bool(np.asarray(ok))
        np.testing.assert_array_equal(np.asarray(out), _ref(mat, idx))

    def test_skewed_spans_flagged(self, rng):
        # a span overflow must be reported so the dispatch layer can
        # redispatch a plain-gather program
        mat, idx = _mk(1 << 15, 6, 4096, "skewed", rng)
        out, ok = jax.jit(lambda m, i: pg.windowed_take_t(
            m, i, window=1024, interpret=True))(mat, idx)
        assert not bool(np.asarray(ok))

    def test_supported_gate(self):
        assert pg.supported(1 << 20, 1 << 20, 8, 1024)
        assert not pg.supported(512, 1 << 20, 8, 1024)   # mat < window
        assert not pg.supported(1 << 20, 100, 8, 1024)   # seg not tiled

    def test_pick_window(self):
        assert pg.pick_window(0.45) == 1024
        assert pg.pick_window(0.25) == 2048
        assert pg.pick_window(0.05) == pg.MAX_WINDOW


def test_fused_windowed_gather_survives_capacity_padding(env1, rng,
                                                         monkeypatch):
    """Shape-family padding leaves dead rows behind the live prefix of the
    fused join->groupby's sorted state.  Empty segment slots must point at
    the END OF THE LIVE PREFIX: pointing at the capacity made the tile of
    starts that straddles n_groups span the whole pad, overflow every
    window, and silently lose the windowed gather on the chip (first chip
    run of chip_smoke.py, PR 22)."""
    import cylon_tpu as ct
    from cylon_tpu.relational import fused, groupby_aggregate, join_tables

    n = 66000                      # cap 69632: 3632 dead rows per side
    mk = lambda: rng.integers(0, int(n * 0.9), n).astype(np.int64)  # noqa: E731
    lt = ct.Table.from_pydict({"k": mk(), "a": mk()}, env1)
    rt = ct.Table.from_pydict({"k": mk(), "b": mk()}, env1)
    assert lt.capacity - n > 2048
    calls = []
    orig = fused._fused_fn

    def builder(mesh, *static):
        fn = orig(mesh, *static)

        def call(*args):
            calls.append((static, args, fn(*args)))
            return calls[-1][2]
        return call
    monkeypatch.setattr(fused, "_fused_fn", builder)
    groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"), "k",
                      [("a", "sum"), ("b", "sum")]).to_pandas()
    monkeypatch.undo()
    static, args, plain = calls[-1]
    assert static[10] == 0 and static[8] % pg.TILE == 0
    # interpret mode on the CPU; jax 0.9's Pallas interpreter cannot type
    # varying axes inside shard_map, so this one program skips that check
    from functools import partial
    monkeypatch.setattr(fused, "shard_map",
                        partial(jax.shard_map, check_vma=False))
    win = orig(env1.mesh, *static[:10], 4096, *static[11:])(*args)
    n_groups, wok = np.asarray(win[4]).reshape(-1, 2)[0]
    assert wok == 1, "the windowed gather reported a span overflow"
    assert n_groups == np.asarray(plain[4]).reshape(-1, 2)[0][0]
    for a, b in zip(jax.tree.leaves(win[:4]), jax.tree.leaves(plain[:4])):
        np.testing.assert_array_equal(np.asarray(a)[:n_groups],
                                      np.asarray(b)[:n_groups])
