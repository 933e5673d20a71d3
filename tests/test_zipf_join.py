"""Skewed keys through the normal path (ISSUE 32): a fact table whose join
key is bounded Zipf over ``[0, 0.9 n)`` - the benchmark's
``benchmark/dists/zipf.py``, written out again here - joined to a table
with uniform keys and summed by the key, on one device and on four, through
the fused pushdown and through the materialized join, each result compared
cell for cell with a numpy reference written in this file; why a settled
grouped reduce ran XLA's gather (``grouped_reduce_plain_dispatches{reason}``,
``fused.window_rule``); and what the groupby plan node says it settled on.
"""

import types

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import config, obs
from cylon_tpu.relational import fused, groupby_aggregate, join_tables

from test_groupby_dispatch import _plain_counters, _plain_delta

ROWS = 100_000
AGGS = [("a", "sum"), ("b", "sum")]


def _zipf(rng, rows: int, s: float, fraction: float = 0.9):
    """Exact bounded Zipf by inverse CDF, ranks renamed by a permutation."""
    n = int(rows * fraction)
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -s)
    cdf /= cdf[-1]
    rank = np.minimum(np.searchsorted(cdf, rng.random(rows), side="right"),
                      n - 1)
    return rng.permutation(n)[rank].astype(np.int64)


def _host_tables(s: float, seed: int = 5, rows: int = ROWS,
                 probe_keys=_zipf):
    rng = np.random.default_rng(seed)
    uni = lambda: rng.integers(0, int(rows * 0.9), rows).astype(  # noqa: E731
        np.int64)
    left = {"k": probe_keys(rng, rows, s), "a": uni()}
    return left, {"k": uni(), "b": uni()}


def _reference(left: dict, right: dict) -> dict:
    """Inner join on ``k`` with the joined rows written out (every left
    row once per right row of its key), then the two sums key by key;
    rows by key."""
    order = np.argsort(right["k"], kind="stable")
    rk, rb = right["k"][order], right["b"][order]
    lo = np.searchsorted(rk, left["k"], side="left")
    times = np.searchsorted(rk, left["k"], side="right") - lo
    jk = np.repeat(left["k"], times)
    ja = np.repeat(left["a"], times)
    # the right rows of left row i are rk[lo[i] : lo[i] + times[i]]
    within = np.arange(len(jk)) - np.repeat(np.cumsum(times) - times, times)
    jb = rb[np.repeat(lo, times) + within]
    keys, inv = np.unique(jk, return_inverse=True)
    a_sum = np.zeros(len(keys), np.int64)
    b_sum = np.zeros(len(keys), np.int64)
    np.add.at(a_sum, inv, ja)
    np.add.at(b_sum, inv, jb)
    return {"k": keys, "a_sum": a_sum, "b_sum": b_sum}


def _node(qplan, op: str):
    return next(r for r in qplan.roots if r.op == op)


@pytest.mark.parametrize("s", [1.1, 1.5])
@pytest.mark.parametrize("route", ["fused_pushdown", "materialized"])
@pytest.mark.parametrize("world", ["env1", "env4"])
def test_zipf_join_groupby_equals_numpy(world, route, s, request):
    """At s = 1.1 the hottest key holds ~13% of the probe rows, at 1.5
    over a third: on four devices the first key's owner is projected to
    hold 1.39x the balanced rows, under the measured bound of
    ``skew.split_rule`` (PR 34: route ``hash``, armed, no key split),
    the second's over twice (``skew_split``, one heavy key)."""
    env = request.getfixturevalue(world)
    left, right = _host_tables(s)
    hot_share = np.bincount(left["k"]).max() / ROWS
    assert (0.10 < hot_share < 0.16) if s == 1.1 else hot_share > 1 / 3
    lt = ct.Table.from_pydict(left, env)
    rt = ct.Table.from_pydict(right, env)

    def query():
        joined = join_tables(lt, rt, "k", "k", how="inner")
        if route == "materialized":
            joined.columns                         # forces the expansion
        return groupby_aggregate(joined, "k", AGGS)

    qplan = obs.explain(query)
    got = qplan.result.to_pandas().sort_values("k")
    want = _reference(left, right)
    assert len(got) == len(want["k"])
    for name, col in want.items():
        assert got[name].dtype == np.int64
        np.testing.assert_array_equal(got[name].to_numpy(), col, name)

    join, gb = _node(qplan, "join"), _node(qplan, "groupby")
    if env.world_size == 1:
        assert join.attrs["route"] == "colocated"
    elif s == 1.1:
        assert join.attrs["skew_owner_load"] < join.attrs[
            "skew_owner_load_bound"] < 2.0
        assert join.attrs["route"] == "hash"
        assert join.attrs["skew_split_armed"] is True
        assert join.attrs["skew_split_keys"] == 0
    else:
        assert join.attrs["skew_owner_load"] > 2.0
        assert join.attrs["route"] == "skew_split"
        assert join.attrs["skew_plan"]["keys"] == 1
    if route == "fused_pushdown":
        assert gb.attrs["route"] == "fused_pushdown"
    else:
        assert gb.attrs["route"] in ("grouped_fastpath", "combine_shuffle")
    # what the dispatcher settled on, on the plan node: never a window on
    # this rig, the remembered segment bucket, the density the rule saw
    assert gb.attrs["window"] == 0
    assert 0.0 < gb.attrs["density"] < 0.2
    assert gb.attrs["segment_space"] >= 512 or env.world_size > 1
    if env.world_size == 1 and route == "fused_pushdown":
        n_groups = len(want["k"])
        assert gb.attrs["segment_space"] == config.pow2ceil(n_groups)
        assert gb.attrs["density"] == round(n_groups / (2 * ROWS), 6)


def test_plan_fields_are_the_same_on_a_warm_callsite(env1):
    """First sight (512 slots, one re-dispatch) and the warm call write
    the same ``segment_space`` / ``density`` / ``window``: the static tree
    of a query does not change between runs."""
    left, right = _host_tables(1.1, seed=11)
    lt = ct.Table.from_pydict(left, env1)
    rt = ct.Table.from_pydict(right, env1)
    query = lambda: groupby_aggregate(                         # noqa: E731
        join_tables(lt, rt, "k", "k", how="inner"), "k", AGGS)
    first = obs.explain(query)
    warm = obs.explain_analyze(query, profile_keys=False)
    assert first.static_dict() == warm.static_dict()
    assert {"segment_space", "density", "window"} <= set(
        _node(warm, "groupby").attrs)
    assert "density=" in warm.render() and "segment_space=" in warm.render()


def _mesh_of(platform: str):
    dev = types.SimpleNamespace(platform=platform)
    return types.SimpleNamespace(devices=np.array([dev], dtype=object))


@pytest.mark.parametrize("platform,seg_cap,density,why", [
    ("cpu", 1 << 22, 0.5, "not_tpu"),
    ("tpu", 1 << 22, 0.033, "density_below_floor"),
    ("tpu", 1 << 19, 0.033, "density_below_floor"),    # the first test that fails
    ("tpu", 1 << 19, 0.5, "segment_space_small"),
    ("tpu", 2228224, 0.2, ""),
    ("tpu", 15204352, 0.6, ""),
])
def test_window_rule_names_the_test_that_failed(platform, seg_cap, density,
                                                why):
    """``fused.window_rule`` is the one statement of the eligibility rule:
    the window and, where it is 0, the reason; ``window_for`` is its
    window alone."""
    from cylon_tpu.ops import pallas_gather as pg
    mesh = _mesh_of(platform)
    window, got = fused.window_rule(mesh, seg_cap, density)
    assert got == why
    assert window == (0 if why else pg.pick_window(density)) and \
        (window > 0) == (not why)
    assert fused.window_for(mesh, seg_cap, density) == window


def _uniform(rng, rows, _s):
    return rng.integers(0, int(rows * 0.9), rows).astype(np.int64)


@pytest.mark.parametrize("as_tpu,probe_keys,why", [
    (False, _zipf, "not_tpu"),
    (True, _zipf, "density_below_floor"),
    (True, _uniform, "segment_space_small"),
])
def test_plain_dispatch_is_counted_under_its_reason(as_tpu, probe_keys, why,
                                                    env1, monkeypatch):
    """End to end through the fused callsite: each query settles ONE
    dispatch, counted once under the word ``fused.window_rule`` gave -
    with the rule told it is on a TPU, the skewed table is under the
    density floor (0.057 at this size, 0.033 at the benchmark's) and the
    uniform one, dense enough, has too small a segment space."""
    if as_tpu:
        rule = fused.window_rule
        monkeypatch.setattr(fused, "window_rule", lambda mesh, sc, dens:
                            rule(_mesh_of("tpu"), sc, dens))
    left, right = _host_tables(1.1, seed=23, probe_keys=probe_keys)
    lt = ct.Table.from_pydict(left, env1)
    rt = ct.Table.from_pydict(right, env1)
    before = _plain_counters()
    assert set(before) >= {"no_window_rule", "span_overflow", "not_tpu",
                           "density_below_floor", "segment_space_small",
                           "remembered_plain"}      # registered at import
    for n_queries in (1, 2):
        g = groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"),
                              "k", AGGS)
        assert g.row_count == len(_reference(left, right)["k"])
        assert _plain_delta(before) == {why: n_queries}
