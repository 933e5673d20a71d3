"""Skewed keys across chips (ISSUE 34): a fact table whose join key is
bounded Zipf(1.1) with a hot set that belongs to the deployment -
``benchmark/dists/zipf_fixed_hot.py``, written out again here - joined to a
uniform dimension and summed by the key on four devices, on the route the
heavy-key rule takes by itself and on the other one, each equal to a numpy
join written in ``test_zipf_join.py``; what the exchange's span and counters
say of how uneven it was, against numpy's own per-destination counts under
the engine's hash; what an unsplit join's plan node says the rule saw; and
the rule itself, ``skew.split_rule``, as a table.
"""

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import config, obs
from cylon_tpu.obs import trace
from cylon_tpu.ops import hashing
from cylon_tpu.relational import groupby_aggregate, join_tables, skew

from test_zipf_join import AGGS, _host_tables, _node, _reference

ROWS = 120_000
HOT_SEED = 7            # the configuration's (cylon_join_zipf_8m_x4.json)


def _zipf_fixed_hot(rng, rows: int, s: float, fraction: float = 0.9):
    """Exact bounded Zipf by inverse CDF; the rank -> key permutation is
    HOT_SEED's, the uniforms the run's."""
    n = int(rows * fraction)
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -s)
    cdf /= cdf[-1]
    rank = np.minimum(np.searchsorted(cdf, rng.random(rows), side="right"),
                      n - 1)
    return np.random.default_rng(HOT_SEED).permutation(n)[rank].astype(
        np.int64)


def _per_dest(keys: np.ndarray, w: int):
    """numpy's count of the rows a source sends a destination (rows are
    partitioned evenly, in order), under the engine's routing hash."""
    import jax
    tgt = np.asarray(jax.jit(lambda k: hashing.partition_targets(
        hashing.hash_rows([k], [None]), w))(keys))
    cells = np.stack([np.bincount(c, minlength=w)
                      for c in np.array_split(tgt, w)])
    return cells, cells.sum(axis=0)


@pytest.fixture(scope="module")
def tables(env4):
    left, right = _host_tables(1.1, seed=2**31 + 34, rows=ROWS,
                               probe_keys=_zipf_fixed_hot)
    return (left, right, ct.Table.from_pydict(left, env4),
            ct.Table.from_pydict(right, env4))


def _query(lt, rt):
    return groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"),
                             "k", AGGS)


@pytest.mark.parametrize("route", ["by_the_rule", "the_other_one"])
def test_both_routes_equal_the_numpy_join(tables, route, monkeypatch):
    """The hottest key holds ~13.5% of the probe rows: its owner's
    projected load ``1 + 0.135 * 3`` is under the bound, so the rule takes
    ``hash``; with the RULE patched (no environment variable) to split at
    that load, the same tables take ``skew_split`` - one key, fan-out 2,
    the fused pushdown combining the members' partials - and both results
    equal numpy's cell for cell."""
    left, right, lt, rt = tables
    hot_share = np.bincount(left["k"]).max() / ROWS
    assert 0.10 < hot_share < 0.16
    load, bound, by_itself = skew.split_rule(hot_share, 4)
    assert load == 1 + 3 * hot_share and bound == 1.75
    assert by_itself is False
    if route == "the_other_one":
        monkeypatch.setattr(skew, "split_rule", lambda share, w: (
            1.0 + share * (w - 1), 1.3, 1.0 + share * (w - 1) > 1.3))
    detected = obs.counter("skew_detect_joins").value
    split_keys = obs.counter("skew_split_keys").value
    qplan = obs.explain(lambda: _query(lt, rt))
    got = qplan.result.to_pandas().sort_values("k")
    want = _reference(left, right)
    assert len(got) == len(want["k"])
    for name, col in want.items():
        assert got[name].dtype == np.int64
        np.testing.assert_array_equal(got[name].to_numpy(), col, name)
    join, gb = _node(qplan, "join"), _node(qplan, "groupby")
    assert gb.attrs["route"] == "fused_pushdown"
    assert obs.counter("skew_detect_joins").value == detected + 1
    # whichever way it went, the join node says what the rule saw: the
    # sketch's estimate of the hottest key's share and its owner's load
    assert join.attrs["skew_top_share"] == pytest.approx(hot_share, abs=0.02)
    assert join.attrs["skew_owner_load"] == pytest.approx(
        1 + 3 * join.attrs["skew_top_share"], abs=1e-5)
    if route == "by_the_rule":
        assert join.attrs["skew_owner_load_bound"] == 1.75
        assert join.attrs["route"] == "hash"
        assert join.attrs["skew_split_armed"] is True
        assert join.attrs["skew_split_keys"] == 0
        assert join.attrs["skew_owner_load"] < 1.75
        assert obs.counter("skew_split_keys").value == split_keys
    else:
        assert join.attrs["skew_owner_load_bound"] == 1.3
        assert join.attrs["route"] == "skew_split"
        assert join.attrs["skew_plan"]["keys"] == 1
        assert join.attrs["skew_plan"]["fanout"] == [2]
        assert gb.attrs["skew_partials_combined"] == 1
        assert obs.counter("skew_split_keys").value == split_keys + 1


def test_exchange_says_how_uneven_it_was(tables, env4):
    """``recv_max`` / ``recv_cap`` / ``block`` / ``rounds`` on each
    ``exchange.flat`` span and the two counters equal numpy's counts under
    the engine's hash: the probe side's fullest chip well over its
    balanced share, the build side's not."""
    left, right, lt, rt = tables
    w = env4.world_size
    _query(lt, rt).to_pandas()                                  # warm
    names = ("exchange_recv_max_rows_total", "exchange_recv_cap_rows_total",
             "exchange_rows_total", "exchange_count")
    before = {n: obs.counter(n).value for n in names}
    rec = trace.arm(capacity=1024)
    _query(lt, rt).to_pandas()
    exch = [e[6] for e in rec.events()
            if e[2] == "X" and e[3] == "exchange.flat"]
    delta = {n: obs.counter(n).value - before[n] for n in names}
    assert len(exch) == delta["exchange_count"] == 2     # left, then right
    want_max = want_cap = 0
    for args, keys in zip(exch, (left["k"], right["k"])):
        cells, per_dest = _per_dest(keys, w)
        block = config.pow2ceil(min(int(cells.max()), config.pow2ceil(
            max(2 * -(-ROWS // (w * w)), 8192))))
        assert args["rows"] == ROWS
        assert args["recv_max"] == int(per_dest.max())
        assert args["recv_cap"] == config.pow2ceil(int(per_dest.max()))
        assert args["block"] == block
        assert args["rounds"] == -(-int(cells.max()) // block) == 1
        want_max += args["recv_max"]
        want_cap += args["recv_cap"]
    assert delta["exchange_recv_max_rows_total"] == want_max
    assert delta["exchange_recv_cap_rows_total"] == want_cap
    assert delta["exchange_rows_total"] == 2 * ROWS
    balanced = ROWS / w
    assert exch[0]["recv_max"] > 1.2 * balanced      # the skewed probe side
    assert exch[1]["recv_max"] < 1.05 * balanced     # the uniform build side
    assert exch[0]["recv_cap"] > exch[1]["recv_cap"]


def test_uniform_keys_run_the_detector_and_find_nothing(env4):
    """No heavy key in uniform tables: the detector runs (one join, one
    count), no key is split, and the node still says what the rule saw."""
    rng = np.random.default_rng(5)
    uni = lambda: rng.integers(0, 9_000, 10_000).astype(np.int64)  # noqa: E731
    lt = ct.Table.from_pydict({"k": uni(), "a": uni()}, env4)
    rt = ct.Table.from_pydict({"k": uni(), "b": uni()}, env4)
    detected = obs.counter("skew_detect_joins").value
    split = obs.counter("skew_split_joins").value
    qplan = obs.explain(lambda: _query(lt, rt))
    join = _node(qplan, "join")
    assert join.attrs["route"] == "hash"
    assert join.attrs["skew_split_keys"] == 0
    assert join.attrs["skew_top_share"] < 0.01
    assert join.attrs["skew_owner_load"] < 1.03
    assert obs.counter("skew_detect_joins").value == detected + 1
    assert obs.counter("skew_split_joins").value == split


def test_one_device_runs_no_detector(env1):
    rng = np.random.default_rng(6)
    uni = lambda: rng.integers(0, 900, 1_000).astype(np.int64)  # noqa: E731
    lt = ct.Table.from_pydict({"k": uni(), "a": uni()}, env1)
    rt = ct.Table.from_pydict({"k": uni(), "b": uni()}, env1)
    detected = obs.counter("skew_detect_joins").value
    qplan = obs.explain(lambda: _query(lt, rt))
    assert _node(qplan, "join").attrs["route"] == "colocated"
    assert "skew_top_share" not in _node(qplan, "join").attrs
    assert obs.counter("skew_detect_joins").value == detected


_ZIPF_1_1 = 0.1137       # the hottest key's share at the benchmark's size


@pytest.mark.parametrize("w,share,split", [
    # the bound is met where the key alone is a chip's balanced rows: 1 / w
    (2, 0.30, False), (2, 0.49, False), (2, 0.51, True), (2, 0.95, True),
    (4, _ZIPF_1_1, False), (4, 0.24, False), (4, 0.26, True), (4, 0.40, True),
    (8, 0.05, False), (8, _ZIPF_1_1, False), (8, 0.13, True), (8, 0.40, True),
])
def test_rule_table(w, share, split):
    """``split_rule`` at worlds 2, 4, 8 around the bound: the owner's
    projected load ``1 + share * (w - 1)`` against ``2 - 1/w`` - Zipf(1.1)'s
    hottest key stays whole on four chips (1.34x against 1.75, measured:
    the split does not pay there) and on config 5's own eight (1.80x
    against 1.875)."""
    load, bound, got = skew.split_rule(share, w)
    assert load == pytest.approx(1 + share * (w - 1), abs=1e-12)
    assert bound == {2: 1.5, 4: 1.75, 8: 1.875}[w]
    assert got is split and (load > bound) is split
    assert (share > 1 / w) is split            # the rule as it was worded
    assert skew.split_rule(1.01 / w, w)[2] is True
    assert skew.split_rule(0.99 / w, w)[2] is False


def test_one_chip_never_splits():
    assert skew.split_rule(1.0, 1) == (1.0, 1.0, False)


def test_the_two_environment_variables_are_gone():
    assert not hasattr(config, "SKEW_GLOBAL_FACTOR")
    assert not hasattr(config, "SKEW_SPLIT_SHARE")
    assert config.SKEW_SPLIT is True and config.SKEW_FANOUT_FACTOR == 1.25
