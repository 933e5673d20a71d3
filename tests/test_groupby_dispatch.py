"""The one dispatcher of a grouped reduce
(``relational.groupby.dispatch_at_bucket``: predict the segment bucket,
dispatch, re-dispatch on a mispredict or a window overflow, remember), its
four call sites end to end, the compiler-crash classifier the recovery
ladder's final rung keeps, the dense/scatter segment-reduction parity and
the bounds of the program caches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.ops import groupby as gbk
from cylon_tpu.relational import groupby as rel_gb
from cylon_tpu.relational import groupby_aggregate, join_tables

_BIG = 17 << 19     # a capacity of config.pow2ceil's family, no power of two

#: id: (remembered, cap_full, [(n_groups, win_ok) per dispatch], window the
#:      site would pick, expected [(seg_cap, window) per dispatch],
#:      expected memory)
_DISPATCH_CASES = {
    "first_sight_more_groups_redispatches_once_at_the_true_bucket":
        (None, 4096, [(700, True), (700, True)], 0,
         [(512, 0), (1024, 0)], (1024, True, 0)),
    "first_sight_fewer_groups_is_one_dispatch":
        (None, 4096, [(100, True)], 0, [(512, 0)], (128, True, 0)),
    "warm_hit_dispatches_at_the_remembered_bucket":
        ((128, True, 0), 4096, [(100, True)], 0, [(128, 0)],
         (128, True, 0)),
    "warm_but_grown_redispatches_once_and_remembers":
        ((128, True, 0), 4096, [(300, True), (300, True)], 0,
         [(128, 0), (512, 0)], (512, True, 0)),
    "capacity_at_first_seg_cap_dispatches_at_cap_full":
        (None, 512, [(300, True)], 0, [(512, 0)], (512, True, 0)),
    "capacity_under_first_seg_cap_dispatches_at_cap_full":
        (None, 256, [(9, True)], 0, [(256, 0)], (16, True, 0)),
    "remembered_bucket_not_under_cap_full_dispatches_at_cap_full":
        ((4096, True, 0), 4096, [(4000, True)], 0, [(4096, 0)],
         (4096, True, 0)),
    "true_bucket_never_passes_cap_full":
        (None, _BIG, [(_BIG - 3, True), (_BIG - 3, True)], 0,
         [(512, 0), (_BIG, 0)], (_BIG, True, 0)),
    "window_granted_on_the_redispatch_is_remembered":
        (None, 1 << 22, [(1 << 21, True), (1 << 21, True)], 4096,
         [(512, 0), (1 << 21, 4096)], (1 << 21, True, 4096)),
    "window_overflow_redispatches_without_it_for_good":
        (None, 1 << 22, [(1 << 21, True), (1 << 21, False),
                         (1 << 21, True)], 4096,
         [(512, 0), (1 << 21, 4096), (1 << 21, 0)], (1 << 21, False, 0)),
    "warm_window_is_one_dispatch_with_it":
        ((1 << 21, True, 4096), 1 << 22, [(1 << 21, True)], 1024,
         [(1 << 21, 4096)], (1 << 21, True, 4096)),
    "warm_window_overflow_forbids_it":
        ((1 << 21, True, 4096), 1 << 22, [(1 << 21, False),
                                          (1 << 21, True)], 4096,
         [(1 << 21, 4096), (1 << 21, 0)], (1 << 21, False, 0)),
    "forbidden_window_stays_off_when_the_site_grows":
        ((1 << 20, False, 0), 1 << 22, [(1 << 21, True), (1 << 21, True)],
         4096, [(1 << 20, 0), (1 << 21, 0)], (1 << 21, False, 0)),
    "warm_plain_site_is_not_asked_again_though_its_rule_now_says_yes":
        ((1 << 21, True, 0), 1 << 22, [(1 << 21, True)], 4096,
         [(1 << 21, 0)], (1 << 21, True, 0)),
}


def _window_counters():
    from cylon_tpu.obs import metrics
    return np.array([
        metrics.counter("grouped_reduce_windowed_dispatches").value,
        metrics.counter("grouped_reduce_window_overflows").value])


def _plain_counters() -> dict:
    """``grouped_reduce_plain_dispatches{reason=...}``, by reason."""
    import re
    from cylon_tpu.obs import metrics
    rx = re.compile(r'^grouped_reduce_plain_dispatches\{reason="(\w+)"\}$')
    return {m.group(1): v for k, v in metrics.snapshot().items()
            if (m := rx.match(k))}


def _plain_delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in _plain_counters().items()
            if v != before[k]}


@pytest.mark.parametrize("case", list(_DISPATCH_CASES))
def test_dispatch_at_bucket(case):
    """The whole policy against a fake program: which (segment space,
    window) each dispatch runs at, ONE meta pull per dispatch, nothing
    pulled before ``resolve()``, and what the callsite remembers."""
    from cylon_tpu.relational.common import BoundedCache
    remembered, cap_full, metas, win, want, memory = _DISPATCH_CASES[case]
    cache = BoundedCache()
    if remembered is not None:
        cache.put("site", remembered)
    dispatched, pulled = [], []

    def call(seg_cap, w):
        dispatched.append((seg_cap, w))
        return len(dispatched) - 1

    def read_meta(res):
        pulled.append(res)
        n_groups, win_ok = metas[res]
        # two shards: the bucket follows the larger count
        return np.array([n_groups, n_groups // 2], np.int64), win_ok

    def window(seg_cap, n_groups):
        assert n_groups.shape == (2,)
        return win, "", 0.5

    before, plain_before = _window_counters(), _plain_counters()
    h = rel_gb.dispatch_at_bucket(cache, "site", cap_full, call, read_meta,
                                  window if win else None)
    assert dispatched == want[:1] and pulled == []      # enqueued, not pulled
    res, n_groups = h.resolve()
    assert dispatched == want
    assert pulled == list(range(len(want)))             # one pull a dispatch
    assert res == len(want) - 1 and int(n_groups[0]) == metas[-1][0]
    assert cache["site"] == memory and len(cache) == 1
    # the registry's two counters: dispatches enqueued with a window, and
    # re-dispatches because a window's span overflowed
    assert tuple(_window_counters() - before) == (
        sum(1 for _sc, w in want if w),
        sum(1 for (_sc, w), (_n, ok) in zip(want, metas) if w and not ok))
    # and the third: ONE count a settled dispatch that ran without a
    # window, under the reason - the site gave no rule, a span overflow
    # forbade it, or (this fake rule always says yes) the site remembers 0
    reason = ("no_window_rule" if not win else
              "span_overflow" if not memory[1] else "remembered_plain")
    assert _plain_delta(plain_before) == ({} if want[-1][1]
                                          else {reason: 1})


def _site_query(site, env, rng):
    """(thunk -> result frame, pandas reference, builder module, builder
    name) of a small query that goes through one dispatch site."""
    n = 600
    ldf = pd.DataFrame({"k": rng.integers(0, 40, n).astype(np.int64),
                        "a": rng.integers(0, 99, n).astype(np.int64)})
    lt = ct.Table.from_pandas(ldf, env)
    if site == "fused":
        from cylon_tpu.relational import fused
        rdf = pd.DataFrame({"k": np.arange(40, dtype=np.int64),
                            "b": rng.integers(0, 99, 40).astype(np.int64)})
        rt = ct.Table.from_pandas(rdf, env)
        run = lambda: groupby_aggregate(                       # noqa: E731
            join_tables(lt, rt, "k", "k", how="inner"), "k", [("a", "sum")])
        ref = ldf.merge(rdf, on="k")
        return run, ref, fused, "_fused_fn"
    run = lambda: groupby_aggregate(lt, "k", [("a", "sum")])   # noqa: E731
    return run, ldf, rel_gb, f"_{site}_fn"


@pytest.mark.parametrize("site,world", [("raw", "env1"), ("combine", "env4"),
                                        ("final", "env4"), ("fused", "env1")])
def test_site_recovers_from_a_forced_mispredict(site, world, request, rng,
                                                monkeypatch):
    """Each real call site of the dispatcher, first sight at a 2-slot
    segment space: the mispredict is seen in n_groups, the program runs
    again at the true bucket, the answer is pandas', and the next call is
    one dispatch at the remembered bucket."""
    from cylon_tpu.relational.common import BoundedCache
    env = request.getfixturevalue(world)
    monkeypatch.setattr(rel_gb, "_FIRST_SEG_CAP", 2)
    # the two sites of one distributed groupby share the query: no memory
    # of the other's case
    monkeypatch.setattr(rel_gb, "_SEG_CACHE", BoundedCache())
    run, ref, mod, builder = _site_query(site, env, rng)
    segs = []
    real = getattr(mod, builder)
    seg_at = 8 if site == "fused" else 1      # seg_cap among the statics

    def spy(mesh, *static, **kw):
        segs.append(static[seg_at])
        return real(mesh, *static, **kw)

    monkeypatch.setattr(mod, builder, spy)
    exp = (ref.groupby("k", as_index=False).agg(a_sum=("a", "sum"))
           .sort_values("k").reset_index(drop=True))
    for want_dispatches in (2, 1):
        segs.clear()
        got = run().to_pandas().sort_values("k").reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)
        assert len(segs) == want_dispatches, segs
    assert segs[0] > 2          # the remembered true bucket, not first sight


def _forced_window(monkeypatch, builder):
    """The windowed gather forced onto the CPU rig: ``fused.window_rule``
    answers 1024 whatever the platform and the density, the kernel runs in
    interpret mode (jax 0.9's Pallas interpreter cannot type varying axes
    inside shard_map, so the programs skip that check) and the site starts
    from an empty memory.  Returns the log of ``(statics, arguments,
    outputs)`` per dispatch (``statics[1]`` the segment space,
    ``statics[-2]`` the window), the list of the kernel's traces and the
    builder itself."""
    from functools import partial

    from cylon_tpu.ops import pallas_gather as pg
    from cylon_tpu.relational import fused
    from cylon_tpu.relational.common import BoundedCache
    monkeypatch.setattr(fused, "window_rule",
                        lambda mesh, sc, dens: (1024, ""))
    monkeypatch.setattr(rel_gb, "_SEG_CACHE", BoundedCache())
    monkeypatch.setattr(rel_gb, "shard_map",
                        partial(jax.shard_map, check_vma=False))
    log, traced = [], []
    real, take = getattr(rel_gb, builder), pg.windowed_take_t

    def spy(mesh, *static):
        fn = real(mesh, *static)

        def call(*args):
            log.append((static, args, fn(*args)))
            return log[-1][2]
        return call

    def take_spy(mat_t, idx, window, interpret=None):
        traced.append(window)
        return take(mat_t, idx, window, interpret)

    monkeypatch.setattr(rel_gb, builder, spy)
    monkeypatch.setattr(pg, "windowed_take_t", take_spy)
    return log, traced, real


def _sums_match_pandas_at_windows(t, df, log, windows_per_call):
    """``sum(a) by k`` of ``t``, once per entry of ``windows_per_call``:
    each answer pandas', each call's dispatches at exactly those
    windows."""
    exp = (df.groupby("k", as_index=False).agg(a_sum=("a", "sum"))
           .sort_values("k").reset_index(drop=True))
    for want in windows_per_call:
        log.clear()
        got = (groupby_aggregate(t, "k", [("a", "sum")]).to_pandas()
               .sort_values("k").reset_index(drop=True))
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)
        assert [static[-2] for static, _a, _o in log] == want, log


@pytest.mark.parametrize("site,world", [("raw", "env1"), ("combine", "env4"),
                                        ("final", "env4")])
def test_site_takes_the_window(site, world, request, rng, monkeypatch):
    """The standalone sites ask for the windowed gather under the fused
    path's rule.  A table with dead rows behind its live prefix (empty
    segment slots must point at the END OF THE LIVE PREFIX, PR 22's
    lesson): first sight at 512 slots, then the true bucket WITH the
    window, no span overflow, every output the plain program's and
    pandas'; the second call is one dispatch with the remembered window;
    the registry counts both.  The distributed associative groupby is two
    sites a call (``combine``, then ``final`` over the rows the hash
    exchange delivered): each takes the window and keeps a memory of its
    own."""
    from cylon_tpu import config
    env = request.getfixturevalue(world)
    log, traced, real = _forced_window(monkeypatch, f"_{site}_fn")
    real.cache_clear(env.mesh)     # built by another case: trace it here
    n = 66000                      # cap 69632 on one shard: 3632 dead rows
    df = pd.DataFrame({"k": rng.integers(0, int(n * 0.9), n).astype(np.int64),
                       "a": rng.integers(0, int(n * 0.9), n).astype(np.int64)})
    t = ct.Table.from_pandas(df, env)
    assert t.capacity * env.world_size - n > 1024
    before, plain_before = _window_counters(), _plain_counters()
    _sums_match_pandas_at_windows(t, df, log, ([0, 1024], [1024]))
    assert _plain_delta(plain_before) == {}     # both settled WITH a window
    static, args, win_out = log[-1]
    seg_cap = static[1]
    assert seg_cap > 512 and seg_cap % 256 == 0
    assert traced and set(traced) == {1024}             # the kernel ran
    meta = np.asarray(win_out[-1]).reshape(env.world_size, 2)
    assert meta[:, 1].all(), "the windowed gather reported a span overflow"
    # two windowed dispatches a site; the distributed groupby is two sites
    assert tuple(_window_counters() - before) == (2 if site == "raw" else 4, 0)
    memory = [(seg_cap, True, 1024)]
    if site != "raw":
        # phase 1's bucket holds a shard's distinct keys, phase 2's the
        # groups a shard owns after the exchange
        ends = np.cumsum(np.asarray(t.valid_counts))
        local = max(df["k"].iloc[lo:hi].nunique()
                    for lo, hi in zip(ends - np.asarray(t.valid_counts), ends))
        owned = groupby_aggregate(t, "k", [("a", "sum")]).valid_counts
        both = [(config.pow2ceil(local), True, 1024),
                (config.pow2ceil(int(owned.max())), True, 1024)]
        assert memory[0] == both[site == "final"]    # the spied site's own
        memory = both
    assert list(rel_gb._SEG_CACHE.values()) == memory
    # every output against the plain program's, group by group on each shard
    plain = real(env.mesh, *static[:-2], 0, static[-1])(*args)
    np.testing.assert_array_equal(np.asarray(plain[-1]), meta[:, 0])
    for a, b in zip(jax.tree.leaves(win_out[:-1]),
                    jax.tree.leaves(plain[:-1])):
        a = np.asarray(a).reshape(env.world_size, -1)
        b = np.asarray(b).reshape(env.world_size, -1)
        for r in range(env.world_size):
            np.testing.assert_array_equal(a[r, :meta[r, 0]],
                                          b[r, :meta[r, 0]])


def test_raw_site_window_overflow_falls_back_for_good(env1, rng, monkeypatch):
    """One huge group among many small ones: the tile of starts that holds
    it spans more rows than any window.  The site re-dispatches once
    without the window, the answer is pandas', ``_SEG_CACHE`` remembers
    that the window is not allowed, the next call is ONE plain dispatch
    and the registry counted one overflow."""
    log, traced, _real = _forced_window(monkeypatch, "_raw_fn")
    k = np.concatenate([np.zeros(40000, np.int64),
                        np.arange(1, 26001, dtype=np.int64)])
    rng.shuffle(k)
    df = pd.DataFrame({"k": k,
                       "a": rng.integers(0, 99, len(k)).astype(np.int64)})
    t = ct.Table.from_pandas(df, env1)
    before, plain_before = _window_counters(), _plain_counters()
    _sums_match_pandas_at_windows(t, df, log, ([0, 1024, 0], [0]))
    assert traced                                   # the kernel really ran
    assert list(rel_gb._SEG_CACHE.values()) == [(log[-1][0][1], False, 0)]
    assert tuple(_window_counters() - before) == (1, 1)
    assert _plain_delta(plain_before) == {"span_overflow": 2}   # both calls


class TestCrashClassifier:
    def test_crash_detector(self):
        """``recovery.is_compiler_crash``: what the recovery ladder's final
        rung (``recovery._resumable``) classifies with."""
        from cylon_tpu.exec.recovery import is_compiler_crash
        e = RuntimeError("INTERNAL: tpu_compile_helper subprocess exit "
                         "signal SIGSEGV")
        assert is_compiler_crash(e)
        # a kernel Mosaic REFUSES is an invalid program, not a dead
        # compiler
        assert not is_compiler_crash(RuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel: unsupported"))
        assert not is_compiler_crash(RuntimeError("RESOURCE_EXHAUSTED"))


class TestDenseSegmentParity:
    """The dense one-hot reduction (num_segments <= _DENSE_SEG_MAX) must
    agree exactly with the scatter path it replaces (measured v5e: scatter
    ~72 ns/row at small segment counts from collision serialization, dense
    ~9 ns/row)."""

    @pytest.mark.parametrize("kind", ["sum", "min", "max", "count"])
    @pytest.mark.parametrize("dtype", [np.int64, np.float64, np.int32])
    def test_parity(self, kind, dtype, monkeypatch):
        rng = np.random.default_rng(7)
        n, ns = 4096, 17
        gids = jnp.asarray(rng.integers(0, ns, n).astype(np.int32))
        vals = jnp.asarray(rng.integers(-50, 50, n).astype(dtype))
        mask = jnp.asarray(rng.integers(0, 2, n).astype(bool))
        fn = getattr(gbk, f"seg_{kind}")
        dense = fn(vals, gids, ns, mask)
        monkeypatch.setattr(gbk, "_DENSE_SEG_MAX", 0)
        scatter = fn(vals, gids, ns, mask)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(scatter))

    def test_empty_segment_identities(self):
        gids = jnp.asarray(np.array([0, 0, 2], np.int32))
        vals = jnp.asarray(np.array([5.0, 3.0, 1.0]))
        mn = np.asarray(gbk.seg_min(vals, gids, 4))
        mx = np.asarray(gbk.seg_max(vals, gids, 4))
        assert mn[1] == np.inf and mx[1] == -np.inf
        assert mn[0] == 3.0 and mx[0] == 5.0 and mn[2] == 1.0


def test_all_laneless_f64_key_and_value(env8):
    """Zero-lane vspec (every column laneless f64, none nullable): the sort
    path must ride the index lane alone, not crash in pack_lanes."""
    rng = np.random.default_rng(11)
    df = pd.DataFrame({"k": rng.integers(0, 5, 200).astype(np.float64),
                       "v": rng.random(200)})
    t = ct.Table.from_pandas(df, env8)
    g = groupby_aggregate(t, ["k"], [("v", "sum")]).to_pandas()
    exp = df.groupby("k", as_index=False).agg(v_sum=("v", "sum"))
    g = g.sort_values("k").reset_index(drop=True)
    np.testing.assert_allclose(g["v_sum"].to_numpy(),
                               exp["v_sum"].to_numpy(), rtol=1e-12)


def test_program_caches_bounded():
    """EVERY compiled-program factory in the package must be bounded at
    PROGRAM_CACHE_SIZE — a single reverted `lru_cache(maxsize=None)`
    anywhere fails this (round-2 VERDICT weak #6)."""
    import importlib
    from cylon_tpu import config
    mods = ["cylon_tpu.relational.join", "cylon_tpu.relational.groupby",
            "cylon_tpu.relational.fused", "cylon_tpu.relational.sort",
            "cylon_tpu.relational.setops", "cylon_tpu.relational.repart",
            "cylon_tpu.parallel.shuffle", "cylon_tpu.parallel.collectives",
            "cylon_tpu.exec.pipeline", "cylon_tpu.series"]
    checked = 0
    for mn in mods:
        mod = importlib.import_module(mn)
        for name, obj in vars(mod).items():
            if hasattr(obj, "cache_parameters"):
                ms = obj.cache_parameters()["maxsize"]
                assert ms == config.PROGRAM_CACHE_SIZE, \
                    f"{mn}.{name} cache maxsize={ms}"
                checked += 1
    assert checked >= 30  # the factories really were scanned


def test_program_cache_evicts(env1):
    """Eviction actually happens: more distinct static signatures than a
    (shrunken) cache bound leaves currsize == bound, and the operator
    still computes correctly after eviction."""
    import functools
    from cylon_tpu.relational import groupby as rg
    orig = rg._shrink_fn
    small = functools.lru_cache(maxsize=2)(
        orig.__wrapped__ if hasattr(orig, "__wrapped__") else orig)
    rg._shrink_fn = small
    try:
        for i in range(5):
            df = pd.DataFrame({"k": np.arange(3 + i, dtype=np.int64),
                               "v": np.arange(3 + i, dtype=np.int64)})
            t = ct.Table.from_pandas(df, env1)
            g = groupby_aggregate(t, "k", [("v", "sum")])
            assert g.row_count == 3 + i
        assert small.cache_info().currsize <= 2
    finally:
        rg._shrink_fn = orig
