"""Multi-slice topology tier (cylon_tpu/topo, docs/topology.md): the
hierarchical two-hop exchange must be bit- and order-equal to the flat
plan for every operator riding the exchange engine on a simulated
two-tier CPU grid, the tier-split comm accounting must reconcile with
the always-on counters, the topology plan must vote before the first
hierarchical collective, and the single-slice/unarmed path must add
zero collectives and zero host syncs."""

import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import config
from cylon_tpu.obs import comm, metrics
from cylon_tpu.relational import groupby_aggregate, join_tables, sort_table
from cylon_tpu.relational.repart import repartition, shuffle_table
from cylon_tpu.relational.setops import set_operation
from cylon_tpu.topo import exchange as topo_exchange, model as topo_model


@pytest.fixture
def flat_route(monkeypatch):
    monkeypatch.setattr(config, "TOPO_SHUFFLE", False)
    yield
    monkeypatch.setattr(config, "TOPO_SHUFFLE", True)


def _tables(env, n=3000, seed=11, mv=300):
    rng = np.random.default_rng(seed)
    ldf = pd.DataFrame({"k": rng.integers(0, mv, n).astype(np.int64),
                        "a": rng.random(n)})
    rdf = pd.DataFrame({"k": rng.integers(0, mv, n).astype(np.int64),
                        "b": rng.integers(0, 99, n).astype(np.int64)})
    return (ct.Table.from_pandas(ldf, env), ct.Table.from_pandas(rdf, env),
            ldf, rdf)


def _both_routes(fn):
    """(hierarchical result, flat result) of one thunk — the equality
    harness every operator test runs through."""
    assert config.TOPO_SHUFFLE
    hier = fn()
    prev = config.TOPO_SHUFFLE
    config.TOPO_SHUFFLE = False
    try:
        flat = fn()
    finally:
        config.TOPO_SHUFFLE = prev
    return hier, flat


# ---------------------------------------------------------------------------
# the tier model
# ---------------------------------------------------------------------------

class TestModel:
    def test_env_declaration(self, two_tier):
        t = two_tier.topology
        assert (t.n_slices, t.ranks_per_slice, t.source) == (2, 4, "env")
        assert t.slice_of(0) == 0 and t.slice_of(7) == 1
        assert t.slice_ids().tolist() == [0, 0, 0, 0, 1, 1, 1, 1]
        cross = t.cross_mask()
        assert not cross[0, 3] and cross[0, 4] and cross[7, 1]

    def test_bad_declarations_degrade_to_single(self, env8, monkeypatch):
        # non-dividing, out-of-range and garbage declarations all fall
        # back to single-slice (flat route) — never an error
        for bad in ("3", "16", "1", "0", "nope"):
            monkeypatch.setenv("CYLON_TPU_SLICES", bad)
            topo_model._reslice()
            t = env8.topology
            assert t.n_slices == 1, (bad, t)
            assert topo_model.hier_plan(env8.mesh) is None
        monkeypatch.delenv("CYLON_TPU_SLICES")
        topo_model._reslice()

    def test_gateway_and_plan_identity(self, two_tier):
        # destination (D=1, j=2) buckets on slice 0's local rank 2
        assert topo_model.gateway_of(6, 0, 4) == 2
        assert topo_model.gateway_of(6, 1, 4) == 6
        p1 = topo_model.hier_plan(two_tier.mesh)
        p2 = topo_model.hier_plan(two_tier.mesh)
        assert p1 is p2 and p1.route == "hierarchical"
        # the canonical hash is deterministic across processes/retries
        assert p1.plan_hash() == topo_model.TopologyPlan(
            two_tier.topology, "hierarchical").plan_hash()

    def test_ranks_per_slice_one_routes_flat(self, env8, monkeypatch):
        monkeypatch.setenv("CYLON_TPU_SLICES", "8")
        topo_model._reslice()
        assert env8.topology.n_slices == 8
        # S == W: hop 2 would be the full-axis exchange, hop 1 pure
        # overhead — the plan facade routes flat
        assert topo_model.hier_plan(env8.mesh) is None
        monkeypatch.delenv("CYLON_TPU_SLICES")
        topo_model._reslice()

    def test_slice_major_order(self):
        class D:
            def __init__(self, i, s=None):
                self.id = i
                if s is not None:
                    self.slice_index = s

        interleaved = [D(0, 1), D(1, 0), D(2, 1), D(3, 0)]
        ordered = topo_model.slice_major_order(interleaved)
        assert [d.id for d in ordered] == [1, 3, 0, 2]
        plain = [D(i) for i in range(4)]
        assert topo_model.slice_major_order(plain) == plain

    def test_hop_counts_conservation(self):
        rng = np.random.default_rng(5)
        c = rng.integers(0, 50, (8, 8)).astype(np.int64)
        c1, c2 = topo_exchange.hop_counts(c, 2)
        # hop 1 is slice-local, hop 2 same-local-index only
        sid = np.arange(8) // 4
        assert (c1[sid[:, None] != sid[None, :]] == 0).all()
        loc = np.arange(8) % 4
        assert (c2[loc[:, None] != loc[None, :]] == 0).all()
        # conservation: sources send everything into hop 1, gateways
        # forward exactly what they received, destinations receive the
        # logical column sums
        assert np.array_equal(c1.sum(axis=1), c.sum(axis=1))
        assert np.array_equal(c1.sum(axis=0), c2.sum(axis=1))
        assert np.array_equal(c2.sum(axis=0), c.sum(axis=0))


# ---------------------------------------------------------------------------
# bit/order equality per operator (the tentpole contract)
# ---------------------------------------------------------------------------

class TestEquality:
    def test_shuffle_join_groupby(self, two_tier):
        lt, rt, _, _ = _tables(two_tier)
        sh, sf = _both_routes(
            lambda: shuffle_table(lt, ["k"]).to_pandas())
        pd.testing.assert_frame_equal(sh, sf)   # exact incl. row order
        for how in ("inner", "left", "outer"):
            jh, jf = _both_routes(
                lambda h=how: join_tables(lt, rt, "k", "k",
                                          how=h).to_pandas())
            pd.testing.assert_frame_equal(jh, jf)
        gh, gf = _both_routes(
            lambda: groupby_aggregate(
                join_tables(lt, rt, "k", "k", how="inner"), "k",
                [("a", "sum"), ("b", "sum")]).to_pandas())
        pd.testing.assert_frame_equal(gh, gf)

    def test_sort_repartition_setops(self, two_tier):
        lt, _, ldf, _ = _tables(two_tier, seed=12)
        sh, sf = _both_routes(lambda: sort_table(lt, "k").to_pandas())
        pd.testing.assert_frame_equal(sh, sf)
        rh, rf = _both_routes(
            lambda: repartition(shuffle_table(lt, ["k"])).to_pandas())
        pd.testing.assert_frame_equal(rh, rf)
        rng = np.random.default_rng(13)
        at = ct.Table.from_pandas(
            pd.DataFrame({"k": rng.integers(0, 50, 800).astype(np.int64)}),
            two_tier)
        bt = ct.Table.from_pandas(
            pd.DataFrame({"k": rng.integers(0, 50, 800).astype(np.int64)}),
            two_tier)
        for op in ("intersect", "union", "subtract"):
            oh, of = _both_routes(
                lambda o=op: set_operation(at, bt, o).to_pandas())
            pd.testing.assert_frame_equal(oh, of)

    def test_hot_key_concentration(self, two_tier):
        # an all-to-one distribution drives the multi-round protocol
        # inside the hops; still bit/order-equal
        rng = np.random.default_rng(14)
        df = pd.DataFrame({"k": np.full(60000, 7, np.int64),
                           "a": rng.random(60000)})
        t = ct.Table.from_pandas(df, two_tier)
        n0 = metrics.counter("timing_event_exchange.two_hop").value
        sh, sf = _both_routes(lambda: shuffle_table(t, ["k"]).to_pandas())
        pd.testing.assert_frame_equal(sh, sf)
        assert metrics.counter("timing_event_exchange.two_hop").value > n0

    def test_skew_split_route_under_two_tier(self, two_tier):
        # the adaptive skew-split plan (PR 14) rides the two-hop
        # transport transparently: stitched output still bit/order-equal
        rng = np.random.default_rng(15)
        n = 6000
        hot = np.int64(77)
        sk = rng.integers(0, 600, n).astype(np.int64)
        sk = np.where(rng.random(n) < 0.7, hot, sk)
        bk = rng.integers(0, 600, n).astype(np.int64)
        bk[bk == hot] = hot + 1
        bk[0] = hot
        sl = ct.Table.from_pydict(
            {"k": sk, "a": rng.integers(0, 100, n).astype(np.int64)},
            two_tier)
        sr = ct.Table.from_pydict(
            {"k": bk, "b": rng.integers(0, 100, n).astype(np.int64)},
            two_tier)
        jh, jf = _both_routes(
            lambda: join_tables(sl, sr, "k", "k", how="inner").to_pandas())
        pd.testing.assert_frame_equal(jh, jf)


# ---------------------------------------------------------------------------
# tier accounting + plan vote + unarmed contracts
# ---------------------------------------------------------------------------

class TestAccounting:
    def _armed_shuffle(self, env, lt):
        comm.arm()
        comm.reset()
        r0 = metrics.counter("exchange_rows_total").value
        b0 = metrics.counter("exchange_bytes_total").value
        shuffle_table(lt, ["k"])
        rep = comm.report()
        comm.arm(False)
        comm.reset()
        assert rep["total_rows"] == \
            metrics.counter("exchange_rows_total").value - r0
        assert rep["total_bytes"] == \
            metrics.counter("exchange_bytes_total").value - b0
        return rep

    def test_tier_split_reconciles_and_dcn_messages_quarter(
            self, two_tier):
        lt, _, _, _ = _tables(two_tier, seed=16)
        rep_h = self._armed_shuffle(two_tier, lt)
        prev = config.TOPO_SHUFFLE
        config.TOPO_SHUFFLE = False
        try:
            rep_f = self._armed_shuffle(two_tier, lt)
        finally:
            config.TOPO_SHUFFLE = prev
        for rep in (rep_h, rep_f):
            t = rep["tiers"]
            assert t["n_slices"] == 2
            assert t["ici_rows"] + t["dcn_rows"] == rep["total_rows"]
            assert t["ici_bytes"] + t["dcn_bytes"] == rep["total_bytes"]
            m = np.asarray(t["ici_rows_matrix"]) \
                + np.asarray(t["dcn_rows_matrix"])
            assert np.array_equal(m, np.asarray(rep["rows"]))
        th, tf = rep_h["tiers"], rep_f["tiers"]
        assert th["routes"] == {"two_hop": 1}
        assert tf["routes"] == {"flat": 1}
        # cross-slice PAYLOAD is route-invariant; the MESSAGE count is
        # the two-hop win — exactly 1/R (R = 4) at equal round counts
        assert th["dcn_rows"] == tf["dcn_rows"]
        assert th["dcn_messages"] * 4 == tf["dcn_messages"]
        assert th["dcn_wire_bytes"] <= tf["dcn_wire_bytes"]

    def test_concentrated_counts_cut_dcn_wire_by_ranks_per_slice(
            self, two_tier):
        # a single-source repartition (all rows on rank 0, re-spread
        # evenly) has a one-row count matrix: the flat engine still
        # pads every one of its W−R cross-slice cells per rank to the
        # block, while the two-hop plan's aggregated hop-2 cells stay
        # at W·(S−1) — the DCN WIRE bytes drop by exactly 1/R on this
        # workload class (docs/topology.md "What the two-hop route
        # buys"); payload rows stay route-invariant as always
        rng = np.random.default_rng(19)
        n = 4096
        t = ct.Table.from_pandas(
            pd.DataFrame({"k": rng.integers(0, 999, n).astype(np.int64)}),
            two_tier)
        conc = [n] + [0] * 7
        t0 = repartition(t, rows_per_partition=conc)

        def measure():
            comm.arm()
            comm.reset()
            repartition(t0)
            rep = comm.report()
            comm.arm(False)
            comm.reset()
            return rep["tiers"]

        th, tf = _both_routes(measure)
        assert th["dcn_rows"] == tf["dcn_rows"]
        assert th["dcn_wire_bytes"] * 4 == tf["dcn_wire_bytes"]
        assert th["dcn_messages"] * 4 == tf["dcn_messages"]

    def test_plan_votes_once_per_mesh(self, two_tier):
        lt, _, _, _ = _tables(two_tier, seed=17)
        topo_model._ADOPTED.clear()
        v0 = metrics.counter("topo_plans_voted").value
        shuffle_table(lt, ["k"])
        plan = topo_model.last_plan()
        assert plan is not None and plan.route == "hierarchical"
        assert metrics.counter("topo_plans_voted").value == v0 + 1
        shuffle_table(lt, ["k"])    # same mesh + plan: no re-vote
        assert metrics.counter("topo_plans_voted").value == v0 + 1

    def test_single_slice_armed_is_byte_identical(self, env8):
        # no slice declaration: the ARMED route must take the flat
        # engine verbatim — same results, same exchange counters, no
        # vote, no tier counters (zero extra collectives / host syncs)
        assert env8.topology.n_slices == 1
        assert topo_model.hier_plan(env8.mesh) is None
        lt, rt, _, _ = _tables(env8, seed=18)

        def run():
            r0 = metrics.counter("exchange_rows_total").value
            c0 = metrics.counter("exchange_count").value
            d0 = metrics.counter("exchange_dcn_rows_total").value
            v0 = metrics.counter("topo_plans_voted").value
            out = join_tables(lt, rt, "k", "k", how="inner").to_pandas()
            return (out,
                    metrics.counter("exchange_rows_total").value - r0,
                    metrics.counter("exchange_count").value - c0,
                    metrics.counter("exchange_dcn_rows_total").value - d0,
                    metrics.counter("topo_plans_voted").value - v0)

        (oh, rows_h, cnt_h, dcn_h, vote_h), \
            (of, rows_f, cnt_f, dcn_f, vote_f) = _both_routes(run)
        pd.testing.assert_frame_equal(oh, of)
        assert (rows_h, cnt_h) == (rows_f, cnt_f)
        assert dcn_h == dcn_f == 0
        assert vote_h == vote_f == 0

    def test_recv_guard_sizes_both_tiers(self, two_tier):
        # a remote-slice-concentrated route makes the hop-1 gateway the
        # larger receive tier, and the gateway buffers are still alive
        # while the final buffers fill — the guard bound is the SUM of
        # the tiers (payload + the int32 sidecar lane on hop 1)
        plan = topo_model.hier_plan(two_tier.mesh)
        c = np.zeros((8, 8), np.int64)
        c[0:4, 4] = 1000        # slice 0 → rank (1, 0): gateway (0, 0)
        prep = topo_exchange.prepare(plan, c)
        assert prep.cap1 >= 4000
        rb = 16
        need = topo_exchange.recv_guard_bytes(plan, prep, 4096, rb)
        assert need == prep.cap1 * (rb + 4) + 4096 * rb


# ---------------------------------------------------------------------------
# trimmed chaos soak (the cross-process multislice acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_multislice_pinned():
    """scripts/chaos_soak.py --multislice: the pinned two-tier
    schedules — hierarchical bit-equal to flat with a voted plan and
    ~1/R DCN messages, capacity fault re-adopting the same plan,
    whole-slice SIGKILL resuming via elastic re-shard, and the unarmed
    single-slice zero-extra-collectives leg."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "chaos_soak.py"),
         "--multislice", "--rows", "2000", "--chunks", "3"],
        capture_output=True, text=True, timeout=570, cwd=repo)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert "topo hier -> ok" in p.stdout, p.stdout[-3000:]
    assert "slice-kill + elastic resume -> ok" in p.stdout, p.stdout[-3000:]
    assert "unarmed single-slice -> ok" in p.stdout, p.stdout[-3000:]
