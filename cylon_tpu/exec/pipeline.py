"""Pipelined (chunked) operator execution — the C9 slot, TPU-first.

The reference ships an experimental push-based operator DAG (ops/api/
parallel_op.hpp:32 ``Op`` with per-tag input queues, execution/execution.hpp
:43-110 RoundRobin/ForkJoin/Priority executors, dis_join_op.hpp:44) whose
point is overlapping the shuffle of one batch with the compute of another.
On TPU the executor half of that machinery already exists in the runtime:
XLA dispatch is asynchronous, so a host loop that ENQUEUES piece k+1's
work while piece k still occupies the device gets comm/compute overlap for
free — the design reduces to *streaming tiled operators*, with the tiling
dimension chosen per op:

  set ops tile over ROW chunks (a row's set membership is position-free);
  joins tile over KEY RANGES of the once-sorted build side
  (``pipelined_join``): re-joining row chunks against the full resident
  build would re-sort it per chunk — the measured 7.5x cliff vs the
  monolith — while range pieces sort every row once and make all four
  join types complete per piece (a key's matches cannot leave its range).

Tiling also bounds peak memory: each materialization sizes to one piece's
output instead of the whole op's — the way to run a join whose output (or
sort scratch) exceeds HBM.

Degenerate case C=1 equals the monolithic operator exactly.
"""

from __future__ import annotations

import time as _time
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..obs import plan as _plan
from ..obs import trace as _trace
from ..utils.cache import jit, program_cache
from ..core.column import Column
from ..core.table import Table
from ..ctx.context import ROW_AXIS
from ..relational.common import (PAD_L, REP, ROW, check_same_env,
                                 promote_key_pair)
from ..relational.join import join_tables
from ..relational.piece import PackedPiece, PieceSource  # noqa: F401
from ..relational.repart import concat_tables, shuffle_table
from ..status import CylonError, InvalidError

shard_map = jax.shard_map


def _interleave() -> None:
    """Serving-tier interleave point (docs/serving.md): at piece-loop
    boundaries a session scheduled by :mod:`cylon_tpu.exec.scheduler`
    hands the baton to the next tenant — its already-dispatched async
    device work keeps executing underneath, so the PR 6 overlap
    scheduler keeps the device busy ACROSS tenants.  A no-op (one
    module-global load) outside a scheduler.  Piece boundaries are
    also the periodic metrics-snapshot poll for entrypoints that never
    run the scheduler loop (CYLON_TPU_METRICS_JSON armed) — one
    list load when unarmed."""
    from ..obs import metrics
    metrics.maybe_write_snapshot()
    from . import scheduler
    scheduler.maybe_yield()


@program_cache()
def _chunk_fn(mesh: Mesh, cap: int, step: int):
    """Per-shard dynamic slice [start, start+step) of every column."""

    def per_shard(start, datas, valids):
        def sl(a):
            return jax.lax.dynamic_slice(a, (start,), (step,))

        out_d = tuple(sl(d) for d in datas)
        out_v = tuple(sl(v) if v is not None else None for v in valids)
        return out_d, out_v

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW), out_specs=(ROW, ROW)))


class _LazyChunks(Sequence):
    """Dispatch-on-demand chunk views of one table: ``chunks[i]`` slices
    chunk i when (and each time) it is accessed, so a streaming consumer
    holds ONE chunk's arrays live at a time — the seed dispatched every
    chunk before any consumer ran, pinning all slices at once (the peak
    the pipelined ops' docstrings promise to avoid).  Re-indexing
    re-dispatches: slices are cheap and deterministic."""

    def __init__(self, table: Table, n_chunks: int, step: int):
        self._table = table
        self._n = int(n_chunks)
        self._step = int(step)

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        t = self._table
        items = list(t.columns.items())
        fn = _chunk_fn(t.env.mesh, t.capacity, self._step)
        start = i * self._step
        # chunk validity = how much of each shard's live prefix falls
        # inside [start, start+step)
        vc = np.clip(t.valid_counts - start, 0, self._step)
        out_d, out_v = fn(np.int32(start),
                          tuple(c.data for _, c in items),
                          tuple(c.validity for _, c in items))
        cols = {}
        for (n, c), d, v in zip(items, out_d, out_v):
            cols[n] = Column(d, c.type, v, c.dictionary, bounds=c.bounds)
        return Table(cols, t.env, vc.astype(np.int64))


def chunk_table(table: Table, n_chunks: int) -> Sequence:
    """Split each shard's valid prefix into ``n_chunks`` contiguous row
    ranges; chunk i is a Table holding every shard's i-th range (so the
    concatenation of chunks in order re-covers the table, per shard).
    Returns a lazy sequence: each chunk's device slice dispatches on
    access, not up front."""
    if n_chunks <= 1:
        return [table]
    from ..relational.repart import repad_table
    cap = max(table.capacity, 1)
    step = -(-cap // n_chunks)
    if step * n_chunks != cap:      # make every window in-bounds
        table = repad_table(table, step * n_chunks)
    return _LazyChunks(table, n_chunks, step)


def pipelined_set_op(a: Table, b: Table, op: str, n_chunks: int = 4):
    """Streaming chunked set operation — the reference's ``DisSetOp``
    pipeline stage (cpp/src/cylon/ops/dis_set_op.hpp) re-thought: the
    resident side ``b`` shuffles ONCE, ``a`` streams through in row
    chunks (each chunk shuffled in the loop, interleaving exchange with
    compute — ``a`` is never held shuffled in full), and per-chunk
    partials combine under one final distinct pass:

    union:      distinct(a ∪ b) = unique(concat(unique(chunk_i)…, unique(b)))
    subtract:   rows of a not in b — per-chunk subtract vs resident b,
                then distinct across chunks (a row can recur in chunks)
    intersect:  symmetric to subtract.

    No sink form: set semantics need the cross-chunk distinct pass, so
    partials are not independently consumable.  Peak extra memory is the
    partials (each ≤ one chunk) plus the final distinct input.
    """
    from ..relational.setops import _align_schemas, _set_operation_impl, \
        unique_table
    if op not in ("union", "intersect", "subtract"):
        raise InvalidError(f"unknown set op {op!r}")
    env = check_same_env(a, b)
    with _plan.node("pipelined_set_op", kind=op,
                    n_chunks=int(n_chunks)) as pn:
        if pn:
            pn.set(rows_in=a.row_count + b.row_count)
        a, b = _align_schemas(a, b)
        names = a.column_names
        if env.world_size > 1 and op != "union":
            b = shuffle_table(b, names)     # resident side: ONCE
        parts = []
        for chunk in chunk_table(a, n_chunks):
            _interleave()   # chunk boundary = serving interleave point
            if op == "union":
                # unique_table shuffles internally; a pre-shuffle of `a`
                # would be a redundant third pass over its rows
                parts.append(unique_table(chunk))
            else:
                if env.world_size > 1:
                    chunk = shuffle_table(chunk, names)
                parts.append(_set_operation_impl(chunk, b, op,
                                                 assume_colocated=True))
        if op == "union":
            parts.append(unique_table(b))
        combined = concat_tables(parts) if len(parts) > 1 else parts[0]
        res = unique_table(combined)
        if pn:
            pn.set(rows_out=res.row_count)
        return res


class GroupBySink:
    """Streaming groupby consumer for :func:`pipelined_join` — the
    downstream ``Op`` of the reference's dis-join DAG (dis_join_op.hpp:44
    feeding a groupby op through its queue).

    Each joined chunk is partially aggregated (and released); ``finalize``
    combines the partials.  Ops must decompose through PUBLIC aggregations
    of their partials: sum/count/min/max/mean/var/std (mean = sum & count;
    var/std = sum & count & sumsq — the public ``sumsq`` aggregation is the
    reference's VAR intermediate, compute/aggregate_kernels.hpp:43, exposed
    so the streaming decomposition closes).

    Usage::

        sink = GroupBySink("k", [("a", "sum"), ("b", "mean")])
        pipelined_join(lt, rt, "k", "k", n_chunks=8, sink=sink)
        out = sink.finalize()          # Table, same schema as the
                                       # monolithic groupby_aggregate
    """

    _DECOMP = {"sum": ("sum",), "count": ("count",), "min": ("min",),
               "max": ("max",), "mean": ("sum", "count"),
               "var": ("sum", "count", "sumsq"),
               "std": ("sum", "count", "sumsq")}
    _COMBINE = {"sum": "sum", "count": "sum", "min": "min", "max": "max",
                "sumsq": "sum"}

    def __init__(self, by, aggs, ddof: int = 1):
        self.by = [by] if isinstance(by, str) else list(by)
        self.aggs = list(aggs)
        self.ddof = int(ddof)
        for col, op, *_ in self.aggs:
            if op not in self._DECOMP:
                raise InvalidError(
                    f"GroupBySink does not support {op!r}; supported: "
                    f"{sorted(self._DECOMP)}")
        # one partial agg per distinct (col, intermediate-op)
        self._chunk_aggs = sorted({(c, i) for c, op, *_ in self.aggs
                                   for i in self._DECOMP[op]})
        self._parts: list[Table] = []
        self._regs: list = []  # HBM-ledger registrations of the partials
        self._pending = []   # in-flight fused dispatches (see __call__)
        self._disjoint = False
        self._ckpt = None    # durable-checkpoint Stage (exec/checkpoint)
        self._adopted = 0    # pieces adopted so far = checkpoint index

    def attach_checkpoint(self, stage) -> None:
        """Arm durable checkpointing (exec/checkpoint): each adopted
        partial aggregate — the sink's completed-piece state — is saved
        and committed at its stage boundary.  Adoption order equals
        consumption order (the pending queue is FIFO), so the adoption
        counter IS the piece index."""
        self._ckpt = stage

    def restore_partial(self, part: Table) -> None:
        """Adopt a checkpoint-restored partial (resume fast-forward)
        without re-saving it — bit-identical to the partial the crashed
        process computed, so finalize() is bit-equal to an uninterrupted
        run."""
        from . import memory
        self._parts.append(part)
        self._regs.append(memory.register_table("sink_part", part))
        self._adopted += 1

    def _adopt(self, part: Table) -> None:
        """Keep one chunk's partial aggregate, accounted in the HBM
        ledger (exec/memory): sink state is resident across the whole
        piece loop, so budget decisions must see it.  Released (and the
        balance drained) at finalize."""
        from . import memory
        self._parts.append(part)
        self._regs.append(memory.register_table("sink_part", part))
        if self._ckpt is not None:
            self._ckpt.save_piece(self._adopted, part)
        _trace.async_end("sink.chunk_inflight", self._adopted)
        self._adopted += 1

    def mark_key_disjoint(self) -> None:
        """Caller guarantee: no group key occurs in more than one consumed
        chunk (range-partitioned pipelines keyed on the join keys).
        ``finalize`` then skips the cross-chunk combine groupby — the
        per-chunk partials ARE the final groups and just concatenate."""
        self._disjoint = True

    def __call__(self, chunk: Table) -> None:
        """Consume one chunk.  Deferred inner-join chunks take the fused
        pushdown via begin/resolve: the NEXT chunk's program is enqueued
        before the previous chunk's meta is pulled, so the device never
        idles on the host round trip (one-deep software pipeline; the
        reference's ops-DAG keeps pieces in flight the same way,
        execution.hpp:43)."""
        from ..relational.fused import try_begin_join_groupby
        from ..relational.groupby import _normalize_aggs, groupby_aggregate
        # async trace span per chunk (obs/trace, armed runs only):
        # begins at absorb, ends when the chunk's partial is ADOPTED —
        # for deferred chunks that is one piece later, which is exactly
        # the dispatch/consume overlap the timeline exists to show
        _trace.async_begin("sink.chunk_inflight",
                           self._adopted + len(self._pending))
        specs = _normalize_aggs(list(self._chunk_aggs))
        h = try_begin_join_groupby(chunk, self.by, specs, 1)
        if h is not None:
            self._pending.append(h)
            # one-deep: the next piece's program is enqueued before this
            # pull blocks.  Two-deep was measured SLOWER at the 125M
            # bench (12.91 vs 12.73 s/iter): the extra piece's pinned
            # join state (~1 GB) costs more than the pull overlap gains.
            while len(self._pending) > 1:
                self._settle(self._pending.pop(0))
        else:
            self.flush_pending()
            self._adopt(
                groupby_aggregate(chunk, self.by, list(self._chunk_aggs)))
        return None

    def _settle(self, h) -> None:
        from ..utils import timing
        with timing.sync_region("pipe.consume"):
            # the per-piece host sync of the sink pipeline: its ".block"
            # twin is where the dispatch/block split (bench_smoke.py,
            # CYLON_TPU_TIMING=async) charges the device work that every
            # dispatch-only pipe.* marker above it enqueued
            out = h.resolve()
        self._adopt(out)

    #: public alias of the consume path — the streaming view's verb
    #: (cylon_tpu/stream.view absorbs one micro-batch per call)
    def absorb(self, chunk: Table) -> None:
        self(chunk)

    def flush_pending(self) -> None:
        """Settle every in-flight deferred chunk NOW — the partials
        commit at their stage boundaries as a side effect.  Called
        before a stage is marked complete and before a preemption-grace
        drain raises: both need the durable state to cover every chunk
        the sink has consumed, not just the settled ones."""
        while self._pending:
            self._settle(self._pending.pop(0))

    def compact(self) -> None:
        """Fold the adopted partials into ONE combined partial — bounded
        sink state for unbounded streams.  The combine groupby's summed
        intermediates, renamed back to the partial schema, ARE a valid
        partial (re-summing an already-summed intermediate is the same
        associative fold), so under the streaming exactness contract
        (integer-exact partial sums — docs/streaming.md) a compacted
        sink's snapshot stays bit-equal to the uncompacted one.  Without
        compaction every ``snapshot()`` re-combines one partial per
        absorbed chunk: O(batches) state and per-read cost, quadratic
        over a stream's lifetime.  No-op for 0/1 partials and for
        key-disjoint sinks (their partials are already final groups)."""
        from ..relational.groupby import groupby_aggregate
        self.flush_pending()
        if len(self._parts) <= 1 or self._disjoint:
            return
        partial = concat_tables(self._parts)
        combine = [(f"{c}_{i}", self._COMBINE[i])
                   for c, i in self._chunk_aggs]
        comb = groupby_aggregate(partial, self.by, combine)
        from ..frame import DataFrame
        df = DataFrame(_table=comb).rename(
            {f"{c}_{i}_{self._COMBINE[i]}": f"{c}_{i}"
             for c, i in self._chunk_aggs})
        folded = df[self.by
                    + [f"{c}_{i}" for c, i in self._chunk_aggs]]._table
        from . import memory
        for reg in self._regs:
            memory.release(reg)
        self._parts = [folded]
        self._regs = [memory.register_table("sink_part", folded)]

    def snapshot(self) -> Table:
        """A consistent finalized aggregate over every chunk absorbed SO
        FAR, without disturbing the partials: pending deferred chunks
        are settled (they were already absorbed — settling is part of
        consumption, not a mutation), then the partials combine through
        the shared sink-combine path
        (:func:`cylon_tpu.relational.groupby.combine_sink_partials`)
        while staying adopted — the sink keeps absorbing afterwards.
        This is the streaming ``read()`` primitive
        (:mod:`cylon_tpu.stream.view`): snapshot(k batches) is bit-equal
        to finalize() of a fresh sink fed the same k batches."""
        return self._combine(drain=False)

    def finalize(self) -> Table:
        return self._combine(drain=True)

    def _combine(self, drain: bool) -> Table:
        from ..relational.groupby import combine_sink_partials
        self.flush_pending()
        if not self._parts:
            raise InvalidError("GroupBySink saw no chunks")
        partial = concat_tables(self._parts) if len(self._parts) > 1 \
            else self._parts[0]
        if drain:
            self._parts = []
            from . import memory
            for reg in self._regs:
                memory.release(reg)
            self._regs = []
        return combine_sink_partials(partial, self.by, self.aggs,
                                     self._chunk_aggs, self._COMBINE,
                                     ddof=self.ddof,
                                     disjoint=self._disjoint)


# ---------------------------------------------------------------------------
# scan-pushdown join: stream an out-of-core input straight into the loop
# ---------------------------------------------------------------------------

def pipelined_scan_join(scan, build: Table, scan_on, build_on,
                        how: str = "inner", suffixes=("_x", "_y"),
                        sink=None):
    """Feed a streaming scan (``io.scan_parquet_dist`` — row-group
    batches) DIRECTLY into the pipelined join/groupby loop: the build
    side shuffles ONCE and stays resident; each scan batch is admitted
    against the ledger, shuffled, joined against the resident build and
    consumed (``sink`` absorbs and the batch is released) — so the scan
    side never materializes at full size and the input of an
    out-of-core query never enters the ledger beyond one batch
    (asserted via ``memory.ledger().peak`` in tests/test_io.py).  This
    is the reference's read→partition→operate streaming stack (SURVEY
    §3.5, distributed_io.py:146) on the TPU pipeline.

    Completeness argument: batches partition the scan's ROWS, and every
    scan row's matches live entirely in the resident build — so
    ``inner`` and ``left`` (left = scan side) are complete per batch
    and their union over batches is the full join.  ``right``/``outer``
    would need cross-batch unmatched-build bookkeeping and are typed
    errors here (use :func:`pipelined_join` on a materialized read).
    Dictionary-encoded KEY columns are typed errors too: their codes
    are per-batch, so hash colocation against the once-shuffled build
    would silently diverge — numeric keys (the fact-table case) promote
    batch-independently and are supported."""
    from ..status import CylonIOError
    if how not in ("inner", "left"):
        raise InvalidError(
            "pipelined_scan_join supports how in ('inner','left'): "
            "right/outer need cross-batch unmatched-build bookkeeping — "
            "materialize the read and use pipelined_join instead")
    scan_on = [scan_on] if isinstance(scan_on, str) else list(scan_on)
    build_on = [build_on] if isinstance(build_on, str) else list(build_on)
    env = build.env
    from ..utils import timing
    from . import memory, scheduler
    with _plan.node("pipelined_scan_join", how=how,
                    sink=(type(sink).__name__ if sink is not None
                          else None)) as pn:
        bwork = None
        outs: list = []
        rows_in = 0
        n_batches = 0
        for batch in scan:
            _interleave()   # batch boundary = serving interleave point
            rows_in += batch.row_count
            n_batches += 1
            # per-batch key promotion against the (already promoted,
            # already shuffled) build columns: numeric promotion is
            # batch-independent, so the build side promotes exactly once
            bk = [batch.column(n) for n in scan_on]
            rk = [(build if bwork is None else bwork).column(n)
                  for n in build_on]
            pairs = [promote_key_pair(a, b) for a, b in zip(bk, rk)]
            if any(p.dictionary is not None for pair in pairs
                   for p in pair):
                raise InvalidError(
                    "pipelined_scan_join: dictionary-encoded join keys "
                    "are per-batch-coded and cannot hash-colocate "
                    "against a once-shuffled build — materialize the "
                    "read and use pipelined_join")
            batch = batch.with_columns(
                {n: p for n, (p, _) in zip(scan_on, pairs)})
            if bwork is None:
                bwork = build.with_columns(
                    {n: p for n, (_, p) in zip(build_on, pairs)})
                if env.world_size > 1:
                    bwork = shuffle_table(bwork, build_on)  # ONCE
                memory.register_table("scan_build", bwork)
            # ledger admission per batch (scheduler-mediated, TS109):
            # cold spillable owners evict — and, under a host budget,
            # demote — BEFORE the batch's rows land
            need = sum(int(c.data.nbytes)
                       + (int(c.validity.nbytes)
                          if c.validity is not None else 0)
                       for c in batch.columns.values())
            scheduler.admit_allocation(env, need)
            reg = memory.register_table("scan_batch", batch)
            if env.world_size > 1:
                batch = shuffle_table(batch, scan_on)
            with timing.region("pipe.scan_join"):
                res = join_tables(batch, bwork, scan_on, build_on,
                                  how=how, suffixes=suffixes,
                                  assume_colocated=True,
                                  allow_defer=(sink is not None))
            with timing.region("pipe.consume"):
                outs.append(sink(res) if sink is not None else res)
            memory.release(reg)
        if n_batches == 0:
            raise CylonIOError("pipelined_scan_join: the scan yielded "
                               "no batches")
        if pn:
            pn.set(rows_in=rows_in + build.row_count)
            pn.annotate(route="scan_pushdown", n_batches=n_batches)
        if sink is not None:
            return outs
        out = concat_tables(outs) if len(outs) > 1 else outs[0]
        if pn:
            pn.set(rows_out=out.row_count)
        return out


# ---------------------------------------------------------------------------
# range-partitioned pipelined join
# ---------------------------------------------------------------------------

def _key_op_kinds(dtypes: tuple, need_nf: tuple, narrow: tuple) -> tuple:
    """Static operand KIND tuple of pack.key_operands for this key
    structure — derived next to the packing rules it mirrors
    (ops/pack.key_operand_kinds, the single source of truth); the
    Pallas probe's eligibility gate reads it."""
    from ..ops.pack import key_operand_kinds
    return key_operand_kinds(dtypes, need_nf, narrow)


def _n_key_ops(dtypes: tuple, need_nf: tuple, narrow: tuple) -> int:
    """Static operand count of pack.key_operands for this key structure
    (liveness flag + per-column null flag + 1 or 2 value lanes)."""
    return len(_key_op_kinds(dtypes, need_nf, narrow))


@program_cache()
def _range_bounds_fn(mesh: Mesh, n_ranges: int, narrow: tuple,
                     need_nf: tuple, n_ops: int):
    """Per-shard range boundaries over the LOCALLY SORTED build side:
    candidate positions r*n/R snapped forward to the next key-group start
    (a key's whole run stays in one range), plus the splitter key operands
    at those positions.  A boundary at the live-prefix end (b == n) must
    read as "+infinity" so probe rows never route into the empty trailing
    ranges — each operand is extended by ONE explicit sentinel slot whose
    liveness flag is the pad key (a padding row would serve when n < cap,
    but at exact capacity, n == cap, there is none — gathering the last
    LIVE row there would silently strand that key's probe matches)."""
    from ..ops import pack

    def per_shard(vc, by_datas, by_valids):
        cap = by_datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        n = vc[my]
        mask = jnp.arange(cap) < n
        ko = pack.key_operands(list(by_datas), list(by_valids), row_mask=mask,
                               pad_key=PAD_L, need_null_flags=need_nf,
                               narrow32=narrow)
        bnd = pack.neighbor_flags(ko.ops, ko.kinds)
        pos = jnp.arange(cap, dtype=jnp.int32)
        first = (bnd != 0) | (pos == 0)
        imax = jnp.int32(2**31 - 1)
        nxt = jax.lax.cummin(jnp.where(first, pos, imax), reverse=True)
        cand = (jnp.arange(1, n_ranges, dtype=jnp.int32) * n) // n_ranges
        cand = jnp.clip(cand, 0, cap - 1)
        b = jnp.minimum(nxt[cand], n).astype(jnp.int32)
        sops = []
        for j, op in enumerate(ko.ops):
            sent = jnp.full((1,), PAD_L if j == 0 else 0, op.dtype)
            sops.append(jnp.concatenate([op, sent])[jnp.clip(b, 0, cap)])
        return (b,) + tuple(sops)

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(REP, ROW, ROW),
                             out_specs=(ROW,) * (1 + n_ops)))


@program_cache()
def _probe_targets_fn(mesh: Mesh, n_ranges: int, narrow: tuple,
                      need_nf: tuple, n_ops: int, donate: bool = False,
                      use_pallas: bool = False):
    """Per-row range id for the probe side: count of splitters <= row key
    (>= because splitters are group STARTS of the sorted build).  Dead rows
    get id R so a stable sort by id puts them last.  Also returns per-shard
    per-range live counts.

    ``use_pallas`` routes the splitter probe through the Pallas kernel
    (ops/pallas_probe — splitters resident in SMEM, rows streamed in
    tiles; no (rows, splitters) comparison matrix in HBM); bit-equal to
    the XLA path by construction.  ``donate`` donates the splitter
    operand args (positions 3..3+n_ops) — their only consumer is this
    program, so the steady-state loop reuses their buffers."""
    from ..ops import pack

    def per_shard(vc, by_datas, by_valids, *sops):
        cap = by_datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        n = vc[my]
        mask = jnp.arange(cap) < n
        ko = pack.key_operands(list(by_datas), list(by_valids), row_mask=mask,
                               pad_key=PAD_L, need_null_flags=need_nf,
                               narrow32=narrow)
        if use_pallas:
            from ..ops import pallas_probe
            tgt = pallas_probe.count_ge_splitters(ko.ops, tuple(sops))
        else:
            ge = pack.rows_ge_splitters(ko, tuple(sops))
            # pinned accumulator: jnp.sum(bool) defaults to int64 under
            # x64 — a row-scale widening the jaxpr pass (JX203) flags
            tgt = jnp.sum(ge, axis=1, dtype=jnp.int32)
        tgt = jnp.where(mask, tgt, jnp.int32(n_ranges))
        counts = jnp.zeros(n_ranges + 1, jnp.int32).at[tgt].add(1)
        return tgt, counts[:n_ranges]

    in_specs = (REP, ROW, ROW) + (ROW,) * n_ops
    # a pallas_call in the program: the varying-axes check is off (the
    # program stays pure-local; the jaxpr gate still asserts it contains
    # no collective)
    sm_kwargs = {"check_vma": False} if use_pallas else {}
    jit_kwargs = {"donate_argnums": tuple(range(3, 3 + n_ops))} \
        if donate else {}
    return jit(shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=(ROW, ROW), **sm_kwargs),
                   **jit_kwargs)


def _pull_phase_outputs(devs: list):
    """ONE batched pull of the deferred setup-phase outputs (range
    boundaries + per-range probe counts) — the overlap scheduler's
    designated pre-loop sync point.  Every rank reaches it at the same
    program position (right after the probe-sort dispatch), so a fault
    raised by any deferred phase surfaces HERE, classified onto the
    typed taxonomy, never inside an arbitrary later sync.  The
    ``pipe.phase_sync`` injector site makes that contract testable on
    the CPU rig (tests/test_recovery.py)."""
    from ..utils.host import host_arrays
    from .recovery import maybe_inject
    maybe_inject("pipe.phase_sync")
    try:
        return host_arrays(devs)
    except Exception as e:  # noqa: BLE001 — re-raise typed when classifiable
        from .recovery import classify
        fault = classify(e)
        if fault is None:
            raise
        raise fault from e


class _PieceFuture:
    """One range piece's phase work (packed window descriptors; the
    seed's materialized windows; for spilled sources the async window
    uploads) dispatched AHEAD of its consumption.  A typed fault raised
    while dispatching ahead (piece-cap overflow, injected spill
    pressure) is HELD and re-raised when the piece is CONSUMED — the
    identical consensus-coherent point the non-overlapped schedule
    raises at, so the recovery ladder takes the same rung at the same
    piece with overlap on or off.  Foreign (non-taxonomy) exceptions
    raise immediately: deferring an unclassified error would detach it
    from its dispatch context."""

    __slots__ = ("_pieces", "_fault")

    def __init__(self, thunk, defer_faults: bool = True):
        self._pieces = self._fault = None
        if not defer_faults:
            self._pieces = thunk()
            return
        try:
            self._pieces = thunk()
        except CylonError as e:
            self._fault = e

    def get(self):
        if self._fault is not None:
            raise self._fault
        return self._pieces


def pipelined_join(left: Table, right: Table, left_on, right_on,
                   how: str = "inner", n_chunks: int = 4,
                   suffixes=("_x", "_y"), sink=None):
    """Range-partitioned streaming join (reference DisJoinOP, re-thought
    twice).  The naive streaming form — probe chunks against the full
    resident build — re-sorts the build side per chunk (measured 7.5x below
    the monolith at 96M rows/side).  Instead both sides shuffle once and
    the work tiles over KEY RANGES:

      1. sort the build side ONCE per shard (keys are hash-colocated, so
         ranges are per-shard state — no cross-shard splitter agreement);
      2. snap R-1 evenly spaced positions forward to key-group starts:
         a key's entire build run lives in exactly one range;
      3. assign each probe row its range (vectorized >=-splitters pass) and
         stable-sort the probe side by range id ONCE (columns ride as u32
         lanes);
      4. join range piece pairs — contiguous windows of the two resident
         sorted tables — with the standard two-phase local kernel.

    Total sort work is ~2x the monolith (vs C-times for the naive form)
    while each piece's sort scratch and output stay 1/R-sized.  Because
    ranges partition the KEY space, every join type is complete per piece:
    inner/left/right/outer all stream (an unmatched build row's probe
    matches could only be in its own range — no cross-chunk bookkeeping).

    Note: pieces shuffle with plain hashing — the adaptive skew-split
    plan (relational/skew.py, docs/skew.md) is not applied to the range
    loop's pre-shuffle: range boundaries snap to key-group starts, so a
    salted heavy key would straddle a range's rank group and break the
    per-piece completeness contract every join type stands on (and the
    key-disjoint sink fast path with it).  An extreme single-key
    distribution therefore still concentrates one RANGE's piece on one
    shard — use the monolithic ``join_tables`` for skewed keys, where
    the split + stitch route engages; under EXPLAIN ANALYZE the probe
    side's heavy-hitter profile (``est_rows_per_rank``) is attached to
    this node so the exposure is visible in plan diffs.

    ``sink``: the downstream operator of the pipeline (the reference's next
    ``Op`` in the DAG).  When given, each output piece is passed to
    ``sink(piece_table)`` and immediately released — peak memory is ONE
    piece's output — and the list of sink results is returned.  Piece joins
    then also DEFER (relational/join.py), so a groupby sink on the join
    keys consumes each piece's pre-expansion fused state.  Without a sink
    the pieces are concatenated into one Table (which necessarily holds
    the full output twice during assembly; use a sink for outputs near
    HBM capacity).
    """
    if how not in ("inner", "left", "right", "outer"):
        raise InvalidError(
            "pipelined_join supports how in ('inner','left','right','outer')")
    with _plan.node("pipelined_join", how=how, n_chunks=int(n_chunks),
                    sink=(type(sink).__name__ if sink is not None
                          else None)) as pn:
        if pn:
            pn.set(rows_in=left.row_count + right.row_count)
            # heavy-hitter exposure of the PROBE side (analyze mode
            # only) — the right table for how='right', matching the
            # skew route's probe choice: the pipelined route has no
            # skew split, so the profile's est_rows_per_rank is the
            # "why not this plan" evidence in explain.py diffs
            # (docs/skew.md)
            probe, probe_on = (right, right_on) if how == "right" \
                else (left, left_on)
            po = [probe_on] if isinstance(probe_on, str) else list(probe_on)
            _plan.profile_keys(pn, probe, po)
        res = _pipelined_join_impl(left, right, left_on, right_on, how,
                                   n_chunks, suffixes, sink, pn)
        if pn and type(res) is Table:
            pn.set(rows_out=res.row_count)
        return res


def _pipelined_join_impl(left: Table, right: Table, left_on, right_on,
                         how: str, n_chunks: int, suffixes, sink, pn):
    env = check_same_env(left, right)
    left_on = [left_on] if isinstance(left_on, str) else list(left_on)
    right_on = [right_on] if isinstance(right_on, str) else list(right_on)

    # promote once so every piece shares dictionaries/dtypes with the build
    lkey, rkey = [], []
    for ln, rn in zip(left_on, right_on):
        a, b = promote_key_pair(left.column(ln), right.column(rn))
        lkey.append(a)
        rkey.append(b)
    lwork = left.with_columns(dict(zip(left_on, lkey)))
    rwork = right.with_columns(dict(zip(right_on, rkey)))

    if (sink is not None and isinstance(sink, GroupBySink)
            and left_on == right_on and list(sink.by) == list(left_on)):
        # ranges partition the join-key space, so a groupby sink keyed on
        # the join keys sees each group in exactly one piece
        sink.mark_key_disjoint()

    if env.world_size > 1:
        rwork = shuffle_table(rwork, right_on)   # build side: ONCE
        lwork = shuffle_table(lwork, left_on)    # probe side: ONCE

    n_ranges = max(int(n_chunks), 1)
    if n_ranges == 1 or rwork.row_count == 0 or lwork.row_count == 0:
        if pn:
            pn.annotate(route="monolithic")
        res = join_tables(lwork, rwork, left_on, right_on, how=how,
                          suffixes=suffixes, assume_colocated=True,
                          allow_defer=False)
        return [sink(res)] if sink is not None else res

    from ..relational.sort import local_sort_table
    from ..utils import timing
    # Phase-overlapped scheduling (CYLON_TPU_PACKED_OVERLAP, docs/
    # pipeline.md): the setup phases below — build sort, range bounds,
    # probe targets, probe sort — chain purely on device arrays; nothing
    # between them needs a host value.  With overlap on, each phase is a
    # plain async dispatch and the two host-side sidecars (range
    # boundaries, per-range probe counts) stay ON DEVICE until the one
    # designated sync point after the probe-sort dispatch, where a single
    # batched pull resolves both — the DeferredTable counts-on-device
    # trick (PR 2's join count phase) generalized to every setup phase.
    # Off restores the prior pull-per-phase dispatch behavior.
    overlap = config.PACKED_OVERLAP
    donate = config.DONATE_BUFFERS
    # The phase-1 sorts may donate their input buffers ONLY when those
    # buffers are fresh shuffle outputs this function exclusively owns
    # (world > 1).  At world == 1 lwork/rwork are with_columns views
    # SHARING buffers with the caller's tables — donating them would
    # invalidate user data (use-after-donate, lint rule TS108).
    donate_sort = donate and env.world_size > 1
    with timing.region("pipe.build_sort"):
        rsorted = local_sort_table(rwork, right_on, donate=donate_sort)
        # hash shuffle above co-located equal keys; the per-shard sort
        # makes them contiguous — together that is grouped_by's contract
        rsorted.grouped_by = tuple(right_on)
        timing.maybe_block(next(iter(rsorted.columns.values())).data)
    del rwork
    w = env.world_size

    l_keys = [lwork.column(n) for n in left_on]
    r_keys = [rsorted.column(n) for n in right_on]
    need_nf = tuple((a.validity is not None) or (b.validity is not None)
                    for a, b in zip(l_keys, r_keys))
    from ..relational.common import narrow32_flags
    narrow = narrow32_flags(l_keys, r_keys)
    key_dtypes = tuple(str(c.data.dtype) for c in r_keys)
    op_kinds = _key_op_kinds(key_dtypes, need_nf, narrow)
    n_ops = len(op_kinds)

    from ..relational.common import col_arrays
    from ..utils.host import host_array
    r_datas, r_valids = col_arrays(r_keys)
    vcr = np.asarray(rsorted.valid_counts, np.int32)
    with timing.region("pipe.bounds"):
        res = _range_bounds_fn(env.mesh, n_ranges, narrow, need_nf, n_ops)(
            vcr, r_datas, r_valids)
        b_dev = res[0]
        if not overlap:
            b_host = host_array(b_dev)
    sops = res[1:]

    l_datas, l_valids = col_arrays(l_keys)
    vcl = np.asarray(lwork.valid_counts, np.int32)
    use_pallas = False
    if config.PALLAS_PROBE:
        from ..ops import pallas_probe
        use_pallas = pallas_probe.supported(lwork.capacity, n_ranges - 1,
                                            op_kinds)
    with timing.region("pipe.targets"):
        # sops' only consumer — donated so the loop's steady state reuses
        # their buffers instead of re-allocating per query
        tgt, pc_flat = _probe_targets_fn(env.mesh, n_ranges, narrow, need_nf,
                                         n_ops, donate=donate,
                                         use_pallas=use_pallas)(
            vcl, l_datas, l_valids, *sops)
        if not overlap:
            pc_host = host_array(pc_flat)
    del sops

    from ..core.dtypes import LogicalType
    tmp = "__range__"
    while tmp in lwork:
        tmp += "_"
    ltab = lwork.with_columns(
        {tmp: Column(tgt, LogicalType.INT32, None, bounds=(0, n_ranges))})
    del lwork, tgt
    with timing.region("pipe.probe_sort"):
        # ltab's buffers (fresh shuffle outputs + the fresh range column)
        # are last read here — donated, the sorted output reuses them
        del l_datas, l_valids, l_keys
        lsorted = local_sort_table(ltab, [tmp], donate=donate_sort)
        timing.maybe_block(next(iter(lsorted.columns.values())).data)
    del ltab

    if overlap:
        # THE pre-loop host sync: every setup phase above was dispatched
        # with no intervening pull, so the device executes them as one
        # uninterrupted stream while the host raced ahead to here.
        with timing.sync_region("pipe.phase_sync"):
            b_host, pc_host = _pull_phase_outputs([b_dev, pc_flat])
    b = np.asarray(b_host).reshape(w, n_ranges - 1).astype(np.int64)
    pcounts = np.asarray(pc_host).reshape(w, n_ranges).astype(np.int64)
    n_r = vcr.astype(np.int64)
    bb = np.concatenate([np.zeros((w, 1), np.int64), b, n_r[:, None]], axis=1)
    r_starts = bb[:, :-1]
    r_lens = np.diff(bb, axis=1)
    l_starts = np.concatenate([np.zeros((w, 1), np.int64),
                               np.cumsum(pcounts, axis=1)], axis=1)[:, :-1]

    # all per-range pow2 piece capacities are host-known UP FRONT — the
    # static shape family of every slice/join program the loop will need
    caps_l = [config.pow2ceil(max(int(pcounts[:, r].max()), 1))
              for r in range(n_ranges)]
    caps_r = [config.pow2ceil(max(int(r_lens[:, r].max()), 1))
              for r in range(n_ranges)]
    if pn:
        # the plan-facing piece geometry: route + chunking + dispatch
        # rungs — the static attrs EXPLAIN prints for this node
        pn.annotate(route="range_pipeline", n_ranges=n_ranges,
                    max_cap_l=max(caps_l), max_cap_r=max(caps_r),
                    packed=bool(config.PACKED_PIECES),
                    overlap=bool(overlap), donate=bool(donate))

    # piece-cap-sizing consult of the HBM ledger (exec/memory): admission
    # of the packed sources accounts for the transient sort-operand set
    # the largest piece pair will materialize on top of the resident
    # matrices; under budget pressure, COLD spillable owners evict first
    # (collectively — docs/robustness.md) before the pack allocates
    from ..ops.pack import sort_operand_nbytes
    scratch = sort_operand_nbytes(
        key_dtypes, need_nf, narrow, (max(caps_l) + max(caps_r)) * w)
    with timing.region("pipe.pack"):
        # the sorted tables are exclusively owned here (fresh sort
        # outputs, deleted right below) — donate their columns into the
        # pack programs so the lane matrices reuse those buffers, with
        # the ledger crediting the reuse (exec/memory, docs/pipeline.md)
        src_l = PieceSource(lsorted, max(caps_l), drop=(tmp,),
                            scratch_bytes=scratch, donate=donate)
        src_r = PieceSource(rsorted, max(caps_r), scratch_bytes=scratch,
                            donate=donate)
        timing.maybe_block(src_r.arrs)
    del lsorted, rsorted
    if pn:
        pn.annotate(spilled=bool(src_l.spilled or src_r.spilled))

    packed = config.PACKED_PIECES

    def make_pieces(r):
        """Pieces for range r: packed window descriptors (free — the
        slice+unpack runs inside the join program) or, with the packed
        path disabled, the seed's materialized window tables."""
        if packed:
            return (src_l.packed(l_starts[:, r], pcounts[:, r], caps_l[r]),
                    src_r.packed(r_starts[:, r], r_lens[:, r], caps_r[r]))
        with timing.region("pipe.piece_slice"):
            piece_l = src_l.piece(l_starts[:, r], pcounts[:, r])
            piece_r = src_r.piece(r_starts[:, r], r_lens[:, r])
            timing.maybe_block(next(iter(piece_r.columns.values())).data)
        return piece_l, piece_r

    def qualifies(r):
        any_l = pcounts[:, r].sum() > 0
        any_r = r_lens[:, r].sum() > 0
        return {"inner": any_l and any_r, "left": any_l,
                "right": any_r, "outer": any_l or any_r}[how]

    live_ranges = [r for r in range(n_ranges) if qualifies(r)]

    # ---- durable checkpoint stage (exec/checkpoint) ---------------------
    # Armed only when CYLON_TPU_CKPT_DIR is set — otherwise `stage` stays
    # None and this path adds zero filesystem writes and zero extra
    # collectives.  The plan token pins the stage's static plan; a resume
    # restores committed pieces bit-identically and fast-forwards the
    # loop past them (a corrupt page degrades to recomputing the stage's
    # remaining pieces, never to a wrong answer).
    from . import checkpoint as ckpt
    stage = None
    if (ckpt.enabled() and live_ranges
            and (sink is None or isinstance(sink, GroupBySink))):
        # the consumption MODE is part of the plan: a sink stage
        # checkpoints partial aggregates, a sinkless one piece outputs —
        # restoring one as the other would splice wrong-shaped state in.
        # The token is SPLIT (docs/robustness.md "Elastic resume"): the
        # base names the workload (world-invariant — nothing derived
        # from the shard layout), the full token folds in world size,
        # piece capacities and per-range counts.  A resume matching only
        # the base at a different topology takes the re-shard path.
        mode = ("nosink", tuple(suffixes)) if sink is None else \
            ("sink", tuple(sink.by), tuple(sink._chunk_aggs), sink.ddof)
        # the base carries a world-INVARIANT data fingerprint too — the
        # global live row totals of both sides (per-range counts are
        # layout-derived, their sums are not): without it an elastic
        # resume over CHANGED inputs would base-match a stale
        # checkpoint and adopt another dataset's answers, the guard the
        # same-world full token already provides
        base = ckpt.plan_token("pipelined_join", how, tuple(left_on),
                               tuple(right_on), n_ranges, mode,
                               int(pcounts.sum()), int(r_lens.sum()))
        token = ckpt.plan_token(
            base, w, tuple(caps_l), tuple(caps_r),
            tuple(int(x) for x in pcounts.sum(axis=0)),
            tuple(int(x) for x in r_lens.sum(axis=0)))
        stage = ckpt.open_stage(env, "pipelined_join", token,
                                base_token=base)
        if pn:
            pn.annotate(ckpt=True)
        if isinstance(sink, GroupBySink):
            sink.attach_checkpoint(stage)

    start = 0
    outs = []
    adopted_whole = False
    if stage is not None and ckpt.resume_requested():
        from ..status import CheckpointCorruptError, DataIntegrityError
        from . import recovery
        restored: list = []
        foreign = stage.foreign is not None
        if stage.resuming:
            while (len(restored) < len(live_ranges)
                   and stage.has_piece(len(restored))):
                try:
                    restored.append(stage.load_piece(len(restored)))
                except (CheckpointCorruptError, DataIntegrityError) as e:
                    # an armed manifest-fingerprint miss degrades exactly
                    # like page corruption: recompute, never adopt
                    ckpt.corrupt_fallback(stage, len(restored), e)
                    break
        elif foreign and stage.foreign_complete:
            # world-mismatch re-shard: the WHOLE stage (and only a whole
            # stage — old-layout pieces have no expressible complement
            # in the new layout) is adopted, stitched and re-blocked
            # onto this mesh; any corruption degrades to recompute
            try:
                restored = stage.load_foreign_pieces()
            except (CheckpointCorruptError, DataIntegrityError) as e:
                ckpt.corrupt_fallback(stage, len(restored), e)
                restored = []
        # rank-coherent fast-forward: every rank adopts the MINIMUM
        # restorable prefix across ranks (one vote per stage; entered by
        # every rank whenever resume is requested, even with nothing
        # restorable locally — including ranks that have no own rank dir
        # because the world GREW) — a rank-local fallback would leave
        # the recomputing rank alone in the per-piece commit collectives
        # below
        start = recovery.ckpt_resume_consensus(getattr(env, "mesh", None),
                                               len(restored))
        if foreign:
            # all-or-nothing: a rank that verified fewer foreign pieces
            # degrades EVERY rank's adoption to recompute (foreign
            # restores were not yet counted, so nothing to unrestore)
            if start != len(restored) or not restored:
                start = 0
                restored = []
            else:
                ckpt.note_reshard(start)
                adopted_whole = True
                # first post-reshard commit: rewrite the adopted state
                # under THIS topology's layout token at the next
                # manifest generation — the second resume at this world
                # is then a plain fast-forward, and the old world's
                # leftover rank dirs read as stale forever
                stage.begin_rewrite()
                for i, tbl in enumerate(restored):
                    stage.save_piece(i, tbl)
                stage.mark_complete()
                start = len(live_ranges)   # the whole piece loop is done
        elif len(restored) > start:
            ckpt.unrestore(len(restored) - start)
        for tbl in restored[:(len(restored) if adopted_whole else start)]:
            if sink is not None:
                sink.restore_partial(tbl)
                outs.append(None)   # a GroupBySink call returns None too
            else:
                outs.append(tbl)

    if packed and live_ranges[start:]:
        # pre-warm: with the capacities known, every distinct join
        # program can AOT-compile BEFORE the range loop (while the probe
        # sort still occupies the device) instead of stalling dispatch
        # mid-stream.  No-op where the persistent compile cache is off.
        from ..relational.join import prewarm_packed_join
        warmed = set()
        for r in live_ranges[start:]:
            # the program's static key includes the all-live class (lens
            # exactly at capacity drops the liveness operand), not just
            # the capacity pair — dedupe on the same signature
            key = (caps_l[r], caps_r[r],
                   bool((pcounts[:, r] == caps_l[r]).all()
                        and (r_lens[:, r] == caps_r[r]).all()))
            if key in warmed:
                continue
            warmed.add(key)
            pl0, pr0 = make_pieces(r)
            prewarm_packed_join(pl0, pr0, left_on, right_on, how, suffixes,
                                allow_defer=(sink is not None))

    def _prefetch_ok(r) -> bool:
        """Double-buffer the NEXT piece's host→device uploads against
        this piece's compute when a source is host-resident (spilled):
        upload of piece r+1's window overlaps compute of piece r.  The
        prefetch depth consults the ledger — a budget too tight for two
        window pairs falls back to single-buffering (exec/memory).
        Resident sources skip the prefetch: descriptors are free, and
        creating them early would only reorder CapacityOverflow checks."""
        if not (packed and (src_l.spilled or src_r.spilled)):
            return False
        from . import memory
        pair = w * (caps_l[r] * memory.spec_row_bytes(src_l.spec)
                    + caps_r[r] * memory.spec_row_bytes(src_r.spec))
        return memory.prefetch_depth(pair) > 1

    def piece_future(r):
        # with overlap on, a typed fault raised while dispatching piece
        # r's phases ahead of time is held and re-raised at r's consume
        # point (_PieceFuture) — the recovery ladder sees the identical
        # escalation order as the non-overlapped schedule
        return _PieceFuture(lambda: make_pieces(r), defer_faults=overlap)

    nxt = piece_future(live_ranges[start]) if live_ranges[start:] else None
    for i in range(start, len(live_ranges)):
        # flight-recorder lifecycle (obs/trace, armed runs only): a
        # dispatch span per piece — paired with the sink's async
        # in-flight span, the Perfetto timeline shows piece r+1's
        # dispatch overlapping piece r's consume
        trace_armed = _trace.armed()   # process-uniform (env-armed)
        t_disp = _time.perf_counter() if trace_armed else 0.0
        piece_l, piece_r = nxt.get()
        nxt = None
        if i + 1 < len(live_ranges) and _prefetch_ok(live_ranges[i + 1]):
            # async upload dispatch for piece r+1 (spilled sources) —
            # overlaps the join compute of piece r below
            nxt = piece_future(live_ranges[i + 1])
        with timing.region("pipe.piece_join"):
            # packed pieces: slice + key unpack are fused into this
            # dispatch; with a sink the counts stay on device, so piece
            # r+1's programs enqueue before piece r's host sync (the
            # one-deep software pipeline now spans the WHOLE piece chain)
            res_r = join_tables(piece_l, piece_r, left_on, right_on,
                                how=how, suffixes=suffixes,
                                assume_colocated=True,
                                allow_defer=(sink is not None))
        if trace_armed:
            _trace.complete("pipe.piece_dispatch", t_disp,
                            piece=int(live_ranges[i]))
        with timing.region("pipe.consume"):
            out_r = sink(res_r) if sink is not None else res_r
        if stage is not None and sink is None:
            # sinkless stage boundary: the piece output IS the
            # completed-piece state (a GroupBySink checkpoints its own
            # partials at adoption instead)
            stage.save_piece(i, res_r)
        outs.append(out_r)
        if stage is not None and ckpt.drain_requested(env):
            # preemption grace (exec/preempt): a SIGTERM arrived and the
            # drain vote agreed — the vote must guard the abort on every
            # path (reordering fails the CX403 gate); this piece
            # boundary is the planned
            # exit.  Pending sink chunks settle first (their partials
            # commit), then the typed ResumableAbort carries the resume
            # token out; the relaunch fast-forwards everything committed
            # inside the grace window, re-sharding if the world changed.
            if isinstance(sink, GroupBySink):
                sink.flush_pending()
            ckpt.drain_abort("pipelined_join")
        if nxt is None and i + 1 < len(live_ranges):
            # piece r+1's phase dispatch overlaps piece r's in-flight
            # consumption (the sink's pending pull / deferred counts)
            nxt = piece_future(live_ranges[i + 1])
        # piece boundary = the serving tier's interleave point: piece
        # r's consume (and r+1's dispatch-ahead) are in flight on the
        # device while another tenant's piece enqueues
        _interleave()
    if not outs:
        # no range qualified (e.g. inner join, no overlapping keys at all):
        # one empty piece pair keeps the output schema path uniform
        zeros = np.zeros(w, np.int64)
        if packed:
            piece_l = src_l.packed(zeros, zeros, 1)
            piece_r = src_r.packed(zeros, zeros, 1)
        else:
            piece_l = src_l.piece(zeros, zeros)
            piece_r = src_r.piece(zeros, zeros)
        res_r = join_tables(piece_l, piece_r, left_on, right_on, how=how,
                            suffixes=suffixes, assume_colocated=True,
                            allow_defer=False)
        outs.append(sink(res_r) if sink is not None else res_r)
    if stage is not None:
        # mark the stage COMPLETE (one manifest commit): a later resume
        # at a DIFFERENT topology may only adopt whole stages, and this
        # flag is how it tells a finished stage from a crash prefix.
        # Pending sink chunks settle first so the durable set covers
        # every consumed chunk.
        if isinstance(sink, GroupBySink):
            sink.flush_pending()
        stage.mark_complete()
    if sink is not None:
        return outs
    out = concat_tables(outs) if len(outs) > 1 else outs[0]
    from . import integrity as _integrity
    if _integrity.armed():
        # armed audit (exec/integrity): vote the assembled pipeline
        # output's order-invariant fingerprint rank-coherently at the
        # stage boundary — a rank that stitched different bytes (a
        # corrupted piece that slipped past the per-exchange checks)
        # surfaces typed here instead of as a silently diverged answer
        _integrity.audit_table(out, site="pipe.stitch",
                               phase="post_pipeline")
    if left_on == right_on and not adopted_whole:
        # pieces are key-grouped (sorted merge order) in key-range order and
        # hash-colocated: the concatenation keeps the grouped contract —
        # EXCEPT for state adopted across a topology change, whose rows
        # were re-blocked in global order (per-shard key contiguity and
        # hash colocation are both gone; consumers re-derive)
        out.grouped_by = tuple(left_on)
    return out

# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry): the pipeline's
# own programs are pure-local shard programs — slicing, key-operand
# packing and prefix scans; the exchanges happen upstream in
# parallel/shuffle.py.  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _trace_chunk(mesh):
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    fn = _unwrap(_chunk_fn(mesh, 1024, 256))
    datas = (S((w * 1024,), np.int64), S((w * 1024,), np.float64))
    valids = (S((w * 1024,), np.bool_), None)
    return jax.make_jaxpr(fn)(S((), np.int32), datas, valids)


def _trace_range_bounds(mesh):
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    n_ops = _n_key_ops(("int32",), (False,), (False,))
    fn = _unwrap(_range_bounds_fn(mesh, 4, (False,), (False,), n_ops))
    vc = S((w,), np.int32)
    return jax.make_jaxpr(fn)(vc, (S((w * 1024,), np.int32),), (None,))


def _trace_probe_targets(mesh):
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    n_ranges = 4
    n_ops = _n_key_ops(("int32",), (False,), (False,))
    fn = _unwrap(_probe_targets_fn(mesh, n_ranges, (False,), (False,),
                                   n_ops))
    vc = S((w,), np.int32)
    sops = tuple(S((w * (n_ranges - 1),), np.int32) for _ in range(n_ops))
    return jax.make_jaxpr(fn)(vc, (S((w * 1024,), np.int32),), (None,),
                              *sops)


def _trace_probe_targets_pallas(mesh):
    """The ``CYLON_TPU_PALLAS_PROBE`` dispatch variant: identical
    contract, the splitter probe routed through the Pallas kernel
    (ops/pallas_probe).  Still a pure-local program — the jaxpr walk
    recurses into the pallas_call body, so a collective smuggled into
    the kernel would be a JX205 finding like anywhere else."""
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    n_ranges = 4
    n_ops = _n_key_ops(("int32",), (False,), (False,))
    fn = _unwrap(_probe_targets_fn(mesh, n_ranges, (False,), (False,),
                                   n_ops, use_pallas=True))
    vc = S((w,), np.int32)
    sops = tuple(S((w * (n_ranges - 1),), np.int32) for _ in range(n_ops))
    return jax.make_jaxpr(fn)(vc, (S((w * 1024,), np.int32),), (None,),
                              *sops)


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._chunk_fn", _trace_chunk, tags=("pipeline",))
declare_builder(f"{__name__}._range_bounds_fn", _trace_range_bounds,
                tags=("pipeline",))
declare_builder(f"{__name__}._probe_targets_fn", _trace_probe_targets,
                tags=("pipeline",))
declare_builder(f"{__name__}._probe_targets_fn[pallas]",
                _trace_probe_targets_pallas, tags=("pipeline",))
