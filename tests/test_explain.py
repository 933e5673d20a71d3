"""Tests for the query profiler (cylon_tpu.obs.plan / comm / sketch).

Covers: plan-tree static-shape stability across runs, the EXPLAIN
ANALYZE reconciliation invariant (per-node self seconds sum to the
global phase table), the comm-matrix row/col-sum == exchange-counter
identity, Misra-Gries correctness against exact counts, the heavy-hitter
key profiler's 2×-of-ground-truth acceptance, and the unarmed
zero-collective/zero-write/zero-record contract in the checkpoint tier's
assertion style.  The cross-rank byte-identity of the comm matrix lives
in tests/multihost_driver.py.
"""

import json
import os
import sys

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import config, obs
from cylon_tpu.obs import comm, metrics, plan, sketch
from cylon_tpu.status import InvalidError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    from cylon_tpu.utils import timing
    prev = config.BENCH_TIMINGS
    comm.arm(False)
    comm._rearm()
    comm.reset()
    timing.reset()
    yield
    comm.arm(False)
    comm._rearm()
    comm.reset()
    timing.reset()
    config.BENCH_TIMINGS = prev


def _tables(env, n=4000, hot_frac=0.0, seed=7):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, max(n // 8, 8), n).astype(np.int64)
    if hot_frac > 0.0:
        hot = np.int64(3)
        k = np.where(rng.random(n) < hot_frac, hot, k)
    lt = ct.Table.from_pydict(
        {"k": k, "a": rng.integers(0, 100, n).astype(np.int64)}, env)
    rt = ct.Table.from_pydict(
        {"k": rng.integers(0, max(n // 8, 8), n).astype(np.int64),
         "b": rng.integers(0, 100, n).astype(np.int64)}, env)
    return lt, rt


def _query(lt, rt):
    from cylon_tpu.relational import (groupby_aggregate, join_tables,
                                      sort_table)
    j = join_tables(lt, rt, "k", "k", how="inner")
    g = groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])
    return sort_table(g, "k")


# ---------------------------------------------------------------------------
# plan tree
# ---------------------------------------------------------------------------

class TestPlanTree:
    def test_static_tree_stable_across_runs(self, env4):
        """Same query ⇒ IDENTICAL static tree (ops, attrs, shape)."""
        lt, rt = _tables(env4)
        a = obs.explain(_query, lt, rt).static_dict()
        b = obs.explain(_query, lt, rt).static_dict()
        assert a == b
        # and analyze's static skeleton matches explain's
        c = obs.explain_analyze(_query, lt, rt).static_dict()
        assert a == c

    def test_tree_names_operators_and_routes(self, env4):
        lt, rt = _tables(env4)
        qp = obs.explain(_query, lt, rt)
        ops = {r.op for r in qp.roots}
        assert {"join", "groupby", "sort"} <= ops
        join = next(r for r in qp.roots if r.op == "join")
        assert join.attrs["how"] == "inner"
        assert join.attrs["route"] in ("hash", "broadcast", "skew_split",
                                       "colocated")
        if env4.world_size > 1:
            assert any(c.op == "shuffle" for c in join.children)

    def test_result_passthrough_and_rows(self, env4):
        lt, rt = _tables(env4)
        qp = obs.explain(_query, lt, rt)
        assert qp.result.row_count > 0
        join = next(r for r in qp.roots if r.op == "join")
        assert join.rows_in == lt.row_count + rt.row_count
        # the join DEFERS into the fused groupby pushdown: its node
        # records no rows_out (pulling the deferred counts would break
        # the very deferral being profiled) and the groupby node says so
        g = next(r for r in qp.roots if r.op == "groupby")
        assert g.attrs.get("route") == "fused_pushdown" \
            or g.rows_out == qp.result.row_count
        s = next(r for r in qp.roots if r.op == "sort")
        assert s.rows_out == qp.result.row_count

    @pytest.mark.parametrize("world", [1, 4])
    def test_filter_opens_a_plan_node(self, world, env1, env4):
        """``filter_table`` is plan node ``filter`` (``cylon.op.filter``):
        ``columns``, ``rows_in``, ``rows_out``; the span carries the rows
        too, which it learns inside (``_NodeCtx.span_args``)."""
        import cylon_tpu as ct
        from cylon_tpu.obs import trace
        lt, _ = _tables(env1 if world == 1 else env4)
        df = ct.DataFrame.from_table(lt)
        kept = int((lt.to_pandas()["k"] < 100).sum())
        qp = obs.explain_analyze(lambda: df[df["k"] < 100])
        (node,) = [r for r in qp.roots if r.op == "filter"]
        assert node.attrs["columns"] == lt.column_count
        assert node.rows_in == lt.row_count and node.rows_out == kept
        assert qp.result.table.row_count == kept
        rec = trace.arm(capacity=64)
        try:
            df[df["k"] < 100]
            spans = {e[3]: e[6] for e in rec.events()}
        finally:
            trace.disarm()
        assert spans["op.filter"] == {"rows_in": lt.row_count,
                                      "rows_out": kept}
        assert {"launch.repart__filter_count_fn",
                "launch.repart__filter_mat_fn",
                "launch.series__expr_fn"} <= set(spans)

    def test_pipelined_tree_has_piece_children(self, env4):
        from cylon_tpu.exec import pipelined_join
        lt, rt = _tables(env4, n=6000)
        qp = obs.explain(pipelined_join, lt, rt, "k", "k", how="inner",
                         n_chunks=3)
        root = qp.roots[0]
        assert root.op == "pipelined_join"
        assert root.attrs["route"] == "range_pipeline"
        assert root.attrs["n_ranges"] == 3
        pieces = [c for c in root.children if c.op == "join.piece"]
        assert pieces and all(c.attrs["cap_l"] >= 1 for c in pieces)

    def test_nesting_raises_typed(self, env4):
        lt, rt = _tables(env4, n=256)
        with pytest.raises(InvalidError):
            obs.explain(lambda: obs.explain(_query, lt, rt))

    def test_render_tree_mentions_every_op(self, env4):
        lt, rt = _tables(env4)
        text = obs.explain_analyze(_query, lt, rt).render()
        for op in ("join", "groupby", "sort"):
            assert op in text
        assert "self=" in text and "dispatch" in text


# ---------------------------------------------------------------------------
# analyze: reconciliation + dispatch/block split
# ---------------------------------------------------------------------------

class TestAnalyze:
    def test_totals_reconcile_with_phase_table(self, env4):
        """The acceptance invariant: per-node self seconds sum to the
        global phase table, per region name and in total."""
        lt, rt = _tables(env4)
        qp = obs.explain_analyze(_query, lt, rt)
        rec = qp.reconcile()
        assert rec["phase_s"] > 0
        assert rec["node_s"] <= rec["phase_s"] + 1e-6
        assert abs(rec["unattributed_s"]) \
            <= max(0.05 * rec["phase_s"], 0.02)
        for name, s in rec["per_phase_node_s"].items():
            assert s == pytest.approx(qp.global_phases[name]["s"],
                                      rel=1e-4, abs=2e-3), name

    def test_dispatch_block_split(self, env4):
        lt, rt = _tables(env4)
        qp = obs.explain_analyze(_query, lt, rt)

        def walk(n):
            assert n.seconds is not None
            assert n.dispatch_s is not None and n.block_s is not None
            # phase tables round to 4 decimals; the split sums match
            # to that rounding scale
            assert n.seconds == pytest.approx(
                n.dispatch_s + n.block_s, rel=1e-4, abs=2e-3)
            for c in n.children:
                walk(c)
        for r in qp.roots:
            walk(r)

    def test_caller_flags_restored(self, env4):
        lt, rt = _tables(env4, n=256)
        assert not config.BENCH_TIMINGS
        obs.explain_analyze(_query, lt, rt)
        assert not config.BENCH_TIMINGS

    def test_session_scope_absorbs_node_time(self, env4):
        """A serving-session scope enclosing the profile sees the same
        seconds with profiling on (the absorb-on-pop contract)."""
        from cylon_tpu.utils import timing
        lt, rt = _tables(env4)
        with timing.attribution_scope("tenant") as sc:
            obs.explain_analyze(_query, lt, rt, reset_timings=False)
        assert sc.total_seconds() > 0
        assert "join.shuffle" in sc.snapshot() \
            or "groupby.raw" in sc.snapshot() \
            or "groupby.fused" in sc.snapshot()


# ---------------------------------------------------------------------------
# comm matrix
# ---------------------------------------------------------------------------

class TestCommMatrix:
    def test_row_col_sums_equal_exchange_counters(self, env4):
        lt, rt = _tables(env4)
        comm.arm()
        rows0 = metrics.counter("exchange_rows_total").value
        bytes0 = metrics.counter("exchange_bytes_total").value
        comm.reset()
        _query(lt, rt)
        rep = comm.report()
        drow = metrics.counter("exchange_rows_total").value - rows0
        dbytes = metrics.counter("exchange_bytes_total").value - bytes0
        if env4.world_size == 1:
            assert rep is None and drow == 0
            return
        m_rows = np.asarray(rep["rows"])
        m_bytes = np.asarray(rep["bytes"])
        assert rep["world"] == env4.world_size
        assert rep["exchanges"] >= 3   # two hash shuffles + sort range
        # the identity: matrix grand totals == the always-on counters
        assert int(m_rows.sum()) == rep["total_rows"] == drow
        assert int(m_bytes.sum()) == rep["total_bytes"] == dbytes
        # row/col sums are per-src / per-dst marginals of the same matrix
        assert m_bytes.sum(axis=1).tolist() == rep["row_sums_bytes"]
        assert m_bytes.sum(axis=0).tolist() == rep["col_sums_bytes"]
        # every row routed somewhere: shuffles preserve rows
        assert drow > 0

    def test_single_exchange_marginals(self, env4):
        from cylon_tpu.relational.repart import shuffle_table
        if env4.world_size == 1:
            pytest.skip("no exchange at world 1")
        lt, _ = _tables(env4, n=2000)
        comm.arm()
        comm.reset()
        shuffle_table(lt, ["k"])
        rep = comm.report()
        m = np.asarray(rep["rows"])
        # one hash shuffle moves exactly the table's rows; the row sums
        # are what each source shard held
        assert int(m.sum()) == lt.row_count
        assert m.sum(axis=1).tolist() == [int(x) for x in lt.valid_counts]

    def test_unarmed_profile_never_touches_comm_state(self, env4):
        """Regression (review finding): an UNARMED explain/explain_analyze
        must leave the comm module's cumulative state alone — otherwise a
        later ARMED session's report() serves stale exchanges and its
        totals no longer equal the session's counter deltas."""
        if env4.world_size == 1:
            pytest.skip("no exchange at world 1")
        lt, rt = _tables(env4)
        assert not comm.armed()
        obs.explain(_query, lt, rt)
        obs.explain_analyze(_query, lt, rt)
        assert comm.matrix() is None          # nothing accumulated
        # ...so an armed session's report equals ITS OWN counter deltas
        comm.arm()
        rows0 = metrics.counter("exchange_rows_total").value
        _query(lt, rt)
        rep = comm.report()
        assert rep["total_rows"] \
            == metrics.counter("exchange_rows_total").value - rows0

    def test_profile_keys_opt_out(self, env4):
        """A driver's comparability knob: profile_keys=False skips the
        sampler's device programs; nodes carry no heavy profile."""
        lt, rt = _tables(env4, n=20000, hot_frac=0.9)
        qp = obs.explain_analyze(_query, lt, rt, profile_keys=False)
        def walk(n):
            assert n.heavy is None
            for c in n.children:
                walk(c)
        for r in qp.roots:
            walk(r)

    def test_plan_attaches_comm_report(self, env4):
        lt, rt = _tables(env4)
        comm.arm()
        qp = obs.explain_analyze(_query, lt, rt)
        if env4.world_size > 1:
            assert qp.comm is not None
            assert qp.to_dict()["comm_matrix"]["total_rows"] > 0


# ---------------------------------------------------------------------------
# Misra-Gries + key profiler
# ---------------------------------------------------------------------------

class TestSketch:
    def test_estimates_vs_exact_counts(self):
        rng = np.random.default_rng(5)
        # zipf-ish known distribution over a small alphabet
        vals = rng.choice(np.arange(50), size=20000,
                          p=np.r_[0.4, 0.2, np.full(48, 0.4 / 48)])
        mg = sketch.MisraGries(k=8)
        mg.update(vals)
        exact = {v: int((vals == v).sum()) for v in np.unique(vals)}
        err = mg.error_bound
        assert err <= len(vals) / 9 + 1e-9
        for v, est in mg.items():
            assert exact[int(v)] - err <= est <= exact[int(v)] + 1e-9
        # every value above the MG threshold is tracked
        tracked = {int(v) for v, _ in mg.items()}
        for v, c in exact.items():
            if c > len(vals) / 9:
                assert int(v) in tracked, (v, c)

    def test_weighted_updates(self):
        mg = sketch.MisraGries(k=4)
        mg.update(np.asarray([1, 2, 3]),
                  np.asarray([100.0, 10.0, 1.0]))
        items = dict(mg.items())
        assert items[1] == 100.0 and items[2] == 10.0
        assert mg.n == pytest.approx(111.0)

    def test_k_validation_typed(self):
        with pytest.raises(InvalidError):
            sketch.MisraGries(k=0)


class TestKeyProfile:
    def test_heavy_hitter_within_2x_of_truth(self, env4):
        """The bench --skew acceptance: a 0.9-hot key column reports
        ≥1 heavy hitter whose estimated share is within 2× of truth."""
        lt, _ = _tables(env4, n=20000, hot_frac=0.9)
        truth = float((np.asarray(
            lt.to_pandas()["k"]) == 3).mean())
        prof = plan.key_profile(lt, "k")
        assert prof is not None and prof["heavy"], prof
        top = prof["heavy"][0]
        assert top["key"] == 3
        assert truth / 2 <= top["share"] <= truth * 2, (top, truth)
        assert prof["max_key_share"] >= truth / 2
        assert prof["est_max_rank_share"] >= prof["max_key_share"]

    def test_uniform_keys_report_no_heavy(self, env4):
        lt, _ = _tables(env4, n=20000)
        prof = plan.key_profile(lt, "k")
        assert prof is not None
        assert prof["max_key_share"] < 0.05

    def test_empty_table_returns_none(self, env4):
        lt = ct.Table.from_pydict(
            {"k": np.zeros(0, np.int64)}, env4)
        assert plan.key_profile(lt, "k") is None

    def test_analyze_attaches_node_profile(self, env4):
        lt, rt = _tables(env4, n=20000, hot_frac=0.9)
        qp = obs.explain_analyze(_query, lt, rt)
        join = next(r for r in qp.roots if r.op == "join")
        assert join.heavy is not None
        assert join.heavy["heavy"][0]["key"] == 3


# ---------------------------------------------------------------------------
# the unarmed contract (PR 10 style: zero writes, zero records)
# ---------------------------------------------------------------------------

class TestUnarmedContract:
    def test_no_profile_means_no_nodes_no_records(self, env4, tmp_path,
                                                  monkeypatch):
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("CYLON_TPU_COMM_MATRIX", raising=False)
        assert not plan.active() and not comm.armed()
        # comm.record must never even be CALLED on the unarmed path
        # (the exchange guards on armed()/active()); a call here would
        # raise and fail the query

        def _boom(*a, **k):  # pragma: no cover - the assertion itself
            raise AssertionError("comm.record called while unarmed")
        monkeypatch.setattr(comm, "record", _boom)
        lt, rt = _tables(env4)
        out = _query(lt, rt)
        assert out.row_count > 0
        assert comm.matrix() is None
        assert plan.current() is None
        assert os.listdir(tmp_path) == []

    def test_node_facade_is_noop_without_profile(self):
        with plan.node("join", how="inner") as pn:
            assert not pn
            pn.set(rows_in=5)       # swallowed
            pn.annotate(route="x")  # swallowed
        plan.annotate(route="y")     # no current node: no-op
        assert plan.current() is None

    def test_counters_always_on_but_host_only(self, env4):
        """The exchange totals ride the registry even unarmed — pure
        host arithmetic on the already-pulled sidecar."""
        before = metrics.counter("exchange_rows_total").value
        lt, rt = _tables(env4)
        _query(lt, rt)
        after = metrics.counter("exchange_rows_total").value
        if env4.world_size > 1:
            assert after > before
        else:
            assert after == before


# ---------------------------------------------------------------------------
# histogram edge contract (the obs/metrics satellite) lives in
# tests/test_obs.py; scripts/explain.py CLI round-trip below
# ---------------------------------------------------------------------------

def test_explain_cli_render_and_diff(env4, tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import explain as explain_cli
    finally:
        sys.path.pop(0)
    lt, rt = _tables(env4)
    a = obs.explain_analyze(_query, lt, rt).to_dict()
    b = obs.explain_analyze(_query, lt, rt).to_dict()
    pa = tmp_path / "a.json"
    pa.write_text(json.dumps(a))
    loaded = explain_cli.load_plan(str(pa))
    assert loaded["roots"]
    # bench-JSON wrapping resolves too
    pb = tmp_path / "bench.json"
    pb.write_text(json.dumps({"detail": {"plan": b}}))
    assert explain_cli.load_plan(str(pb))["roots"]
    text = explain_cli.diff_plans(a, b)
    # identical static structure: no structural divergence reported
    assert "structure diverges" not in text
    rendered = explain_cli.render_tree(a)
    assert "join" in rendered
