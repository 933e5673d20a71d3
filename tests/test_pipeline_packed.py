"""Pipelined chunked execution, the packed-piece join entry: packed against
materialized pieces, short windows, the range bounds' sentinel (the join
itself: test_pipeline.py; its sinks: test_pipeline_sinks.py)."""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import config
from cylon_tpu.exec import pipelined_join

from utils import assert_table_matches


class TestPackedPieces:
    """The packed-piece join entry (relational/piece.py + join.py packed
    programs): window slice + lane unpack fused into the join program.
    Contract: EXACTLY equal — same rows, same order, same bits — to the
    seed's materialize-then-join path."""

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_packed_equals_materialized_exactly(self, env4, rng, how):
        n = 3000
        ldf = pd.DataFrame({
            "k": rng.integers(0, 200, n).astype(np.int64),
            "a": rng.random(n),                              # f64 side col
            "c": rng.integers(0, 9, n).astype(np.int32),
            "s": rng.choice(["x", "y", "z"], n).astype(object)})
        rdf = pd.DataFrame({"k": rng.integers(50, 260, n // 2).astype(np.int64),
                            "b": rng.random(n // 2)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        prev = config.PACKED_PIECES
        try:
            config.PACKED_PIECES = True
            got = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=4).to_pandas()
            config.PACKED_PIECES = False
            ref = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=4).to_pandas()
        finally:
            config.PACKED_PIECES = prev
        # exact: both paths must produce identical rows in identical order
        pd.testing.assert_frame_equal(got, ref, check_exact=True)
        exp = ldf.merge(rdf, on="k", how=how)
        assert len(got) == len(exp)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_donation_and_pallas_probe_bit_equal(self, env4, rng, how):
        """Buffer donation (CYLON_TPU_DONATE), the overlap scheduler
        (CYLON_TPU_PACKED_OVERLAP) and the Pallas probe kernel
        (CYLON_TPU_PALLAS_PROBE, interpreter mode on CPU) must each be
        EXACTLY equal — same rows, same order, same bits — to the plain
        per-phase-sync, no-donation dispatch."""
        from cylon_tpu.ops import pallas_probe
        n = 4096  # per-shard capacity 1024: Pallas tile-aligned
        ldf = pd.DataFrame({
            "k": rng.integers(0, 300, n).astype(np.int64),
            "a": rng.random(n),                              # f64 side col
            "s": rng.choice(["x", "y", "z"], n).astype(object)})
        rdf = pd.DataFrame({"k": rng.integers(100, 400, n).astype(np.int64),
                            "b": rng.random(n)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        prev = (config.PACKED_OVERLAP, config.DONATE_BUFFERS,
                config.PALLAS_PROBE)
        probed = []
        orig_supported = pallas_probe.supported

        def spy(cap, n_split, kinds):
            ok = orig_supported(cap, n_split, kinds)
            probed.append(ok)
            return ok

        try:
            config.PACKED_OVERLAP = False
            config.DONATE_BUFFERS = False
            config.PALLAS_PROBE = False
            ref = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=3).to_pandas()
            config.PACKED_OVERLAP = True
            config.DONATE_BUFFERS = True
            got = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=3).to_pandas()
            pd.testing.assert_frame_equal(got, ref, check_exact=True)
            config.PALLAS_PROBE = True
            pallas_probe.supported = spy
            got = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=3).to_pandas()
            pd.testing.assert_frame_equal(got, ref, check_exact=True)
        finally:
            pallas_probe.supported = orig_supported
            (config.PACKED_OVERLAP, config.DONATE_BUFFERS,
             config.PALLAS_PROBE) = prev
        # the eligibility gate must have actually routed the probe
        # through the kernel — a silent fallback would make the pallas
        # leg of this test vacuous
        assert probed == [True]
        exp = ldf.merge(rdf, on="k", how=how)
        assert len(got) == len(exp)

    def test_pallas_probe_kernel_wide_operand_bit_equal(self, rng):
        """Kernel-level bit-equality over the operand shapes the narrow
        single-lane join test can't reach: a MULTI-operand key whose lo
        lane is uint32 (the wide-int64 (hi int32, lo uint32) pack pair —
        ops/pack) with values straddling the 0x80000000 rebase boundary
        and hi-lane ties forcing the lexicographic eq-chain."""
        import jax.numpy as jnp
        from cylon_tpu.ops import pack, pallas_probe
        cap, nsplit = 2048, 13
        hi = rng.integers(-3, 3, cap).astype(np.int32)   # heavy ties
        lo = rng.integers(0, 2**32, cap, dtype=np.uint64).astype(np.uint32)
        lo[:64] = np.uint32(0x80000000)                  # rebase boundary
        lo[64:128] = np.uint32(0x7FFFFFFF)
        live = np.ones(cap, np.int32)
        sel = rng.integers(0, cap, nsplit)
        kinds = ("i", "i", "i")
        assert pallas_probe.supported(cap, nsplit, kinds)
        ops = (jnp.asarray(live), jnp.asarray(hi), jnp.asarray(lo))
        sops = (jnp.asarray(live[sel]), jnp.asarray(hi[sel]),
                jnp.asarray(lo[sel]))
        ge = pack.rows_ge_splitters(pack.KeyOps(ops=ops, kinds=kinds), sops)
        ref = jnp.sum(ge, axis=1, dtype=jnp.int32)
        got = pallas_probe.count_ge_splitters(ops, sops)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_pallas_probe_wide_int64_keys_bit_equal(self, env4, rng):
        """End-to-end: wide int64 keys (bounds past int32, negatives
        included) pack as TWO value operands per key — the Pallas probe
        must engage (eligibility spy) and stay bit-equal to the XLA
        matrix path through the full pipelined join."""
        from cylon_tpu.ops import pallas_probe
        n = 4096
        pool = rng.integers(-2**62, 2**62, 300, dtype=np.int64)
        ldf = pd.DataFrame({"k": rng.choice(pool, n),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.choice(pool, n // 2),
                            "b": rng.random(n // 2)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        prev = config.PALLAS_PROBE
        probed = []
        orig_supported = pallas_probe.supported

        def spy(cap, n_split, kinds):
            ok = orig_supported(cap, n_split, kinds)
            probed.append(ok)
            return ok

        try:
            config.PALLAS_PROBE = False
            ref = pipelined_join(lt, rt, "k", "k", how="inner",
                                 n_chunks=3).to_pandas()
            config.PALLAS_PROBE = True
            pallas_probe.supported = spy
            got = pipelined_join(lt, rt, "k", "k", how="inner",
                                 n_chunks=3).to_pandas()
        finally:
            pallas_probe.supported = orig_supported
            config.PALLAS_PROBE = prev
        assert probed == [True]
        pd.testing.assert_frame_equal(got, ref, check_exact=True)
        assert len(got) == len(ldf.merge(rdf, on="k", how="inner"))

    def test_overlap_one_host_sync_per_piece(self, env4, rng):
        """Acceptance: under the overlap scheduler the range loop costs
        at most ONE sanctioned host pull per piece (the transfer funnel's
        ledger is the counter), and disabling overlap restores the
        per-phase pulls (strictly more) — the escape hatch contract."""
        from cylon_tpu.analysis import runtime
        n = 4096
        lt = ct.Table.from_pydict(
            {"k": rng.integers(0, 2000, n).astype(np.int64),
             "a": rng.integers(0, 50, n).astype(np.int64)}, env4)
        rt = ct.Table.from_pydict(
            {"k": rng.integers(0, 2000, n).astype(np.int64),
             "b": rng.integers(0, 50, n).astype(np.int64)}, env4)

        def pulls(nc, overlap):
            prev = config.PACKED_OVERLAP
            config.PACKED_OVERLAP = overlap
            try:
                with runtime.transfer_scope() as ledger:
                    pipelined_join(lt, rt, "k", "k", how="inner",
                                   n_chunks=nc)
                return sum(ledger.values())
            finally:
                config.PACKED_OVERLAP = prev

        p3, p6 = pulls(3, True), pulls(6, True)
        # dense uniform keys: every range qualifies, pieces == n_chunks.
        # marginal host syncs per extra piece <= 1
        assert p6 - p3 <= 3, (p3, p6)
        # the one batched pre-loop sync beats the per-phase pulls
        assert p3 < pulls(3, False)

    def test_packed_join_defers_with_lazy_counts(self, env4, rng):
        """A packed inner join with allow_defer hands back a DeferredTable
        whose output counts stay ON DEVICE until someone asks — the piece
        loop enqueues the next piece's programs before this one's host
        sync.  Materialization must still be exact."""
        from cylon_tpu.core.table import DeferredTable
        from cylon_tpu.relational.piece import PieceSource
        from cylon_tpu.relational.join import join_tables as jt
        from cylon_tpu.relational.sort import local_sort_table
        n = 2000
        ldf = pd.DataFrame({"k": rng.integers(0, 150, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(0, 150, n).astype(np.int64),
                            "b": rng.integers(0, 50, n).astype(np.int64)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        from cylon_tpu.relational.repart import shuffle_table
        lw = shuffle_table(lt, ["k"])
        rw = shuffle_table(rt, ["k"])
        ls = local_sort_table(lw, ["k"])
        rs = local_sort_table(rw, ["k"])
        src_l = PieceSource(ls, 0)
        src_r = PieceSource(rs, 0)
        w = env4.world_size
        zl = np.zeros(w, np.int64)
        pl = src_l.packed(zl, np.asarray(ls.valid_counts), ls.capacity)
        pr = src_r.packed(zl, np.asarray(rs.valid_counts), rs.capacity)
        out = jt(pl, pr, ["k"], ["k"], how="inner", allow_defer=True)
        assert isinstance(out, DeferredTable) and not out.materialized
        # counts pull on demand; materialization equals the reference join
        ref = jt(lw, rw, ["k"], ["k"], how="inner", assume_colocated=True,
                 allow_defer=False)
        assert out.row_count == ref.row_count
        got = out.to_pandas().sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        exp = ref.to_pandas().sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_exact=True)


class TestPackedWindowPadding:
    """A piece whose ``lens < piece_cap``: the rows of the window past
    ``lens`` are REAL rows of the source (the next range's keys), so a
    program that took one of them for live would join it.  Row liveness
    in the packed count program is the sorted position compare
    (ops/join.live_sides)."""

    @pytest.mark.parametrize("how,defer", [
        ("inner", False), ("inner", True),      # only inner joins defer
        ("left", False), ("right", False), ("outer", False)])
    def test_short_window_matches_pandas(self, env4, rng, how, defer):
        from cylon_tpu.relational.join import join_tables as jt
        from cylon_tpu.relational.piece import PieceSource
        from cylon_tpu.relational.repart import shuffle_table
        from cylon_tpu.relational.sort import local_sort_table
        n = 1500
        ldf = pd.DataFrame({"k": rng.integers(0, 90, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(0, 90, n).astype(np.int64),
                            "b": rng.integers(0, 50, n).astype(np.int64)})
        ls = local_sort_table(shuffle_table(
            ct.Table.from_pandas(ldf, env4), ["k"]), ["k"])
        rs = local_sort_table(shuffle_table(
            ct.Table.from_pandas(rdf, env4), ["k"]), ["k"])
        w = env4.world_size
        zero = np.zeros(w, np.int64)
        # a third of each shard's left rows, two thirds of its right rows
        # (shard 1: no left row at all) in windows of the full capacity
        len_l = np.asarray(ls.valid_counts) // 3
        len_l[1] = 0
        len_r = 2 * np.asarray(rs.valid_counts) // 3
        pl = PieceSource(ls, 0).packed(zero, len_l, ls.capacity)
        pr = PieceSource(rs, 0).packed(zero, len_r, rs.capacity)
        assert (pl.lens < pl.piece_cap).all()
        got = jt(pl, pr, ["k"], ["k"], how=how, allow_defer=defer)
        exp = pl.to_table().to_pandas().merge(pr.to_table().to_pandas(),
                                              on="k", how=how)
        assert got.row_count == len(exp)
        assert_table_matches(got, exp, sort_by=list(exp.columns))


class TestRangeBoundsSentinel:
    """_range_bounds_fn's +inf sentinel edge: a build shard whose live
    prefix is exactly at capacity (n == cap) has NO padding row to serve
    as the boundary sentinel — the explicit sentinel slot must make
    boundary operands read +infinity, or probe rows holding the shard's
    max key silently lose matches (round-4 regression, now for all four
    join types)."""

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_exact_capacity_all_hows(self, env1, rng, how):
        n = 4096  # == pow2 capacity at world 1
        bdf = pd.DataFrame({"k": np.full(n, 7, np.int64),
                            "b": rng.random(n)})
        # probe: the build's max key (must hit all n rows) + a key beyond
        # it (must route to the last range, not vanish past the end)
        pdf = pd.DataFrame({"k": np.where(np.arange(96) % 2 == 0, 7, 9)
                            .astype(np.int64),
                            "a": rng.random(96)})
        lt = ct.Table.from_pandas(pdf, env1)
        rt = ct.Table.from_pandas(bdf, env1)
        assert rt.capacity == rt.row_count  # the no-padding premise
        out = pipelined_join(lt, rt, "k", "k", how=how, n_chunks=4)
        exp = pdf.merge(bdf, on="k", how=how)
        assert out.row_count == len(exp)
        assert_table_matches(out, exp)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_no_qualifying_range_fallback(self, env1, how):
        """With a 2-row build, range 0 snaps empty and all probe keys
        (below the build's min) route there — for inner no range
        qualifies at all (the outs == [] fallback); every how must keep
        the uniform output schema and exact pandas semantics."""
        bdf = pd.DataFrame({"k": np.array([10, 20], np.int64),
                            "b": [1.0, 2.0]})
        pdf = pd.DataFrame({"k": np.array([1, 2, 3], np.int64),
                            "a": [0.1, 0.2, 0.3]})
        lt = ct.Table.from_pandas(pdf, env1)
        rt = ct.Table.from_pandas(bdf, env1)
        out = pipelined_join(lt, rt, "k", "k", how=how, n_chunks=4)
        exp = pdf.merge(bdf, on="k", how=how)
        assert out.row_count == len(exp)
        assert list(out.column_names) == ["k", "a", "b"]
        if len(exp):
            assert_table_matches(out, exp)
