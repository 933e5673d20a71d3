"""Table-level sort: local multi-key sort + distributed sample sort.

TPU-native equivalent of the reference's sort stack — local
``Sort``/``SortIndicesMultiColumns`` (arrow_kernels.hpp:121) and
``DistributedSortRegularSampling`` (table.cpp:620: local sort -> uniform
sample -> splitter selection -> range partition -> ordered exchange -> local
merge).  Differences from the reference forced/afforded by the TPU model:

* splitter selection happens on the controller (single-controller SPMD), so
  the reference's Gather(samples->rank0) + Bcast(splitters) collectives
  (table.cpp:527,536) become a tiny host round-trip of W*m sampled rows;
* the per-rank split-point *binary search* (table.cpp:564-609) becomes a
  vectorized rows>splitters comparison (ops/pack.py rows_gt_splitters) —
  an O(n*W) VPU pass instead of O(n log n) comparator calls;
* the k-way merge of received sorted runs (table.cpp:436) is a plain local
  re-sort: ``lax.sort`` is a bitonic network on the VPU, where merging k runs
  has no advantage over sorting the whole shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..obs import metrics as _metrics
from ..utils.cache import jit, program_cache
from ..core.column import Column
from ..core.table import Table
from ..ctx.context import ROW_AXIS
from ..ops import pack
from ..ops import sort as sortk
from ..status import InvalidError
from ..utils import timing
from ..utils.host import host_array
from ..utils.stages import stage
from .common import (PAD_L, REP, ROW, col_arrays, fold_liveness, live_mask,
                     narrow32_flags, note_liveness, rebuild_like,
                     sample_positions)
from .repart import exchange_by_targets
from ..parallel import shuffle

shard_map = jax.shard_map

#: samples per shard for splitter selection (reference SortOptions.num_samples;
#: 0 = scale with the world size, config.sort_samples)
DEFAULT_SAMPLES = 0

#: one count a sort that took the ``sample_sort`` route (world > 1, rows > 0)
_SAMPLE_SORTS = _metrics.counter("sort_sample_sorts")

def _norm_dirs(by, ascending):
    if isinstance(ascending, bool):
        return tuple(not ascending for _ in by)
    if len(ascending) != len(by):
        raise InvalidError("ascending must match by length")
    return tuple(not a for a in ascending)


@program_cache()
def _local_sort_fn(mesh: Mesh, descendings: tuple, nulls_position: int,
                   narrow: tuple, vspec, f64_idx: tuple = (),
                   by_idx: tuple = (0,), donate: bool = False,
                   fold: bool = False):
    """Per-shard multi-key sort.  Laneable columns RIDE THE SORT as u32
    payload lanes (~1.7 ns/row/lane measured) via ``vspec`` (a LaneSpec
    over the full column list, f64 columns planned laneless); f64 columns
    (positions ``f64_idx``) are gathered once at the stable permutation.

    Key columns are selected from ``datas``/``valids`` by the static
    ``by_idx`` positions rather than passed as separate operands: a key
    buffer must enter the program exactly ONCE for ``donate`` to be
    sound (donating one of two aliases of a buffer is a use-after-donate
    — lint rule TS108).  ``donate`` consumes the caller's column buffers
    (the pipeline's phase-1 sorts, whose inputs are exclusively owned
    fresh shuffle outputs): XLA reuses them for the sorted output
    instead of holding input + output live together.

    Padding sorts last: by a liveness operand, or - ``fold``
    (common.fold_liveness on the first key and its direction) - inside the
    leading key operand, one operand fewer.  The budget below counts a
    folded flag as the operand it was, so no table changes its path
    because an operand came free."""
    from ..ops import lanes
    n_index = 1 if f64_idx else 0     # the row index rides for f64 columns

    def per_shard(vc, datas, valids):
        by_datas = [datas[i] for i in by_idx]
        by_valids = [valids[i] for i in by_idx]
        cap = by_datas[0].shape[0]
        mask = live_mask(vc, cap)
        ko = pack.key_operands(list(by_datas), list(by_valids), row_mask=mask,
                               descendings=list(descendings),
                               nulls_position=nulls_position, pad_key=PAD_L,
                               narrow32=narrow or None, fold=fold)
        if (len(ko.ops) + fold + vspec.n_lanes + n_index
                > pack.SORT_OPERAND_BUDGET or vspec.n_lanes == 0):
            # wide tables (or all-f64, nothing laneable): ONE lane-matrix
            # gather at the permutation (plus f64 side gathers inside
            # gather_columns) beats both per-column gathers and an
            # overloaded sort, whose compile grows with every operand
            perm = sortk.sort_permutation(ko)
            return lanes.gather_columns(vspec, list(datas), list(valids),
                                        perm)
        vmat = lanes.pack_lanes(vspec, list(datas), list(valids))
        payloads = tuple(vmat[:, j] for j in range(vspec.n_lanes))
        need_perm = bool(f64_idx)
        if need_perm:
            payloads += (jnp.arange(cap, dtype=jnp.int32),)
        nk = len(ko.ops)
        with stage("sort_keys"):
            sorted_all = jax.lax.sort(ko.ops + payloads, num_keys=nk,
                                      is_stable=True)
        smat = jnp.stack(sorted_all[nk:nk + vspec.n_lanes], axis=1)
        out_d, out_v = lanes.unpack_lanes(vspec, smat)
        out_d, out_v = list(out_d), list(out_v)
        if need_perm:
            perm = sorted_all[-1]
            with stage("gather_rows"):
                for i in f64_idx:
                    out_d[i] = datas[i][perm]
        return tuple(out_d), tuple(out_v)

    jit_kwargs = {"donate_argnums": (1, 2)} if donate else {}
    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW),
                             out_specs=(ROW, ROW)), **jit_kwargs)


@program_cache()
def _sample_fn(mesh: Mesh, m: int, descendings: tuple, nulls_position: int,
               narrow: tuple = ()):
    """Uniform per-shard sample of transformed key operands (reference
    SampleTableUniform, util/arrow_utils.hpp:125)."""

    def per_shard(vc, by_datas, by_valids):
        cap = by_datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        n = vc[my]
        ko = pack.key_operands(list(by_datas), list(by_valids),
                               descendings=list(descendings),
                               nulls_position=nulls_position,
                               narrow32=narrow or None)
        idx = sample_positions(n, m, cap)
        sampled = tuple(op[idx] for op in ko.ops)
        live = jnp.full((m,), True) & (n > 0)
        return sampled, live

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(REP, ROW, ROW),
                             out_specs=(ROW, ROW)))


@program_cache()
def _target_fn(mesh: Mesh, descendings: tuple, nulls_position: int,
               narrow: tuple = ()):
    """Per-row destination rank = number of splitters strictly below the row
    (vectorized replacement of table.cpp:564-609 split-point binary search).
    ``narrow`` must match the sample fn's so splitter operands compare
    against structurally identical row operands."""

    def per_shard(vc, by_datas, by_valids, splitter_ops):
        cap = by_datas[0].shape[0]
        w = vc.shape[0]
        mask = live_mask(vc, cap)
        ko = pack.key_operands(list(by_datas), list(by_valids),
                               descendings=list(descendings),
                               nulls_position=nulls_position,
                               narrow32=narrow or None)
        gt = pack.rows_gt_splitters(ko, splitter_ops)
        # dtype pins the accumulator: plain sum(bool) widens the (cap, W-1)
        # operand to int64 under x64 (JX203) — W fits int32 trivially
        tgt = jnp.sum(gt, axis=1, dtype=jnp.int32)
        return jnp.where(mask, tgt, jnp.int32(w))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW, REP), out_specs=ROW))


def _pick_splitters(sample_ops, live, w: int):
    """Controller-side splitter selection: sort the W*m sampled operand rows
    (live first), take W-1 evenly spaced rows of the live prefix.  Any choice
    of actual sample rows yields a *correct* partition (rows are compared to
    splitters on device with the same total order); the choice only affects
    balance, so numpy's NaN-last lexsort is fine here."""
    ops_np = [host_array(o) for o in sample_ops]
    live_np = host_array(live)
    # host.sort_splitters: the one host decision between two device
    # programs of a sort - numpy over the W*m pulled sample rows
    with timing.span("host.sort_splitters"):
        n_live = int(live_np.sum())
        # lexicographic argsort over (liveness, op_0, op_1, ...)
        cols = [~live_np] + [o for o in ops_np]
        order = np.lexsort(tuple(reversed(cols)))  # last key primary
        take = []
        for j in range(1, w):
            pos = min(max((n_live * j) // w, 0), max(n_live - 1, 0))
            take.append(order[pos])
        take = np.asarray(take, np.int64)
        return tuple(o[take] for o in ops_np)


#: max u32 order lanes per string key (64 prefix bytes).  Past this the
#: single-process path falls back to exact dense ranks; multi-controller
#: raises (ranks are store-local, not value-stable).
MAX_ORDER_LANES = 16


def _expand_hashed_string_keys(table: Table, by: list, ascending):
    """Rewrite hashed-string sort keys into VALUE-STABLE big-endian byte
    lanes so the numeric sort machinery delivers lexical order.

    Per key: the store's unique values are Arrow-sorted on host, the max
    adjacent common prefix fixes the byte depth D that separates every
    distinct value, and each row's first-D bytes become ceil(D/4) int32
    lane columns (u32 big-endian, sign-flipped).  Lane tuples are equal
    iff the values are equal (D exceeds every distinct-pair common
    prefix), so the output's grouped_by contract still holds for the
    ORIGINAL key names.  Lanes are value-stable — every process computes
    identical lanes from its own store, so multi-controller range
    partitioning agrees without dictionary exchange (beyond one scalar
    max-depth agreement).

    Returns (table2, by2, ascending2, original_by) or None when no key is
    hashed.  Reference: the type-dispatched string sort kernels,
    arrow_kernels.hpp:53 IndexSortKernel<StringArray>."""
    from ..core.column import HashedStrings
    from ..core.dtypes import LogicalType
    from ..core.table import _put
    from .. import native
    env = table.env
    by_cols = [table.column(n) for n in by]
    if not any(isinstance(c.dictionary, HashedStrings) for c in by_cols):
        return None
    import jax
    import pyarrow as pa
    import pyarrow.compute as pc
    descend = _norm_dirs(by, ascending)
    if jax.process_count() > 1:
        # The lane DEPTH must cover the longest common prefix over every
        # DISTINCT value pair; with per-process value stores that bound is
        # not computable locally (process A's 'aaaa1' vs process B's
        # 'aaaa2' share 4 bytes that neither store sees as a pair).  A
        # wrong depth silently mis-sorts, so refuse rather than guess.
        raise InvalidError(
            "multi-controller sort on high-cardinality (hashed) string "
            "keys is not supported: per-process value stores cannot bound "
            "the cross-process common-prefix depth; dictionary-encode the "
            "column (low cardinality) or sort single-controller")
    w, cap = env.world_size, table.capacity
    vc = np.asarray(table.valid_counts, np.int64)
    live = np.zeros(w * cap, bool)
    for i in range(w):
        live[i * cap: i * cap + int(vc[i])] = True
    new_by, new_asc, add_cols = [], [], {}
    for n, c, desc in zip(by, by_cols, descend):
        if not isinstance(c.dictionary, HashedStrings):
            new_by.append(n)
            new_asc.append(not desc)
            continue
        hs, vs = c.dictionary._lookup()
        vs = np.asarray(vs, dtype=object)
        order = np.asarray(pc.sort_indices(
            pa.array(vs, type=pa.large_string())), np.int64)
        depth = native.max_adjacent_lcp(vs[order]) + 1
        n_lanes = -(-depth // 4)
        if n_lanes > MAX_ORDER_LANES:
            # exact dense-rank fallback (store-local, single process)
            ranks = np.empty(len(vs), np.uint32)
            ranks[order] = np.arange(len(vs), dtype=np.uint32)
            lanes = ranks[:, None]
            n_lanes = 1
        else:
            lanes = native.prefix_lanes(vs, n_lanes)        # (U, L) u32
            # +1 LENGTH lane: zero-padding is indistinguishable from a
            # real NUL byte, so values differing only by trailing NULs
            # ('ab' vs 'ab\0') encode identically at any depth — byte
            # length breaks exactly that tie (a strict prefix sorts
            # before its extensions, matching bytewise order)
            lens = native.utf8_lengths(vs).astype(np.uint32)
            lanes = np.concatenate([lanes, lens[:, None]], axis=1)
            n_lanes += 1
        codes = host_array(c.data)
        cu = codes.view(np.uint64) if codes.dtype == np.int64 \
            else codes.astype(np.uint64)
        if len(hs):
            idx = np.clip(np.searchsorted(hs, cu), 0, len(hs) - 1)
            ok = live if c.validity is None \
                else live & host_array(c.validity)
            if bool((hs[idx][ok] != cu[ok]).any()):
                raise InvalidError(
                    f"sort on string column {n!r}: some rows' codes are "
                    "missing from this process's value store (shuffled-in "
                    "rows from another controller); materialize first")
            row_lanes = lanes[idx]
        else:
            row_lanes = np.zeros((len(cu), n_lanes), np.uint32)
        # ONE device upload for all of this key's lanes (each buffer
        # pays its own transfer latency), sliced into columns device-side
        mat = (row_lanes ^ np.uint32(0x80000000)).view(np.int32)
        placed = _put(np.ascontiguousarray(mat), env.sharding())
        for li in range(n_lanes):
            lane_host = mat[:, li]
            name = f"__strord_{n}_{li}"
            while name in table:
                name += "_"
            bounds = ((int(lane_host.min()), int(lane_host.max()))
                      if lane_host.size else None)
            add_cols[name] = Column(placed[:, li], LogicalType.INT32,
                                    c.validity, bounds=bounds)
            new_by.append(name)
            new_asc.append(not desc)
    return table.with_columns(add_cols), new_by, new_asc, list(by)


def sort_table(table: Table, by, ascending=True,
               nulls_position: str = "last",
               num_samples: int = DEFAULT_SAMPLES,
               method: str = "initial") -> Table:
    """Sort ``table`` globally by key columns ``by``.

    ``method`` selects the reference's two sample-sort strategies
    (table.cpp:761 dispatch):

    * ``"initial"`` (default) — ``DistributedSortInitialSampling``
      (table.cpp:692): sample the UNSORTED shards, range-partition, one
      local sort.  One sort pass; splitter quality rests on uniform
      position sampling.
    * ``"regular"`` — ``DistributedSortRegularSampling`` (table.cpp:620):
      LOCAL SORT first, then sample the sorted runs — evenly spaced
      positions of a sorted shard are its exact per-shard quantiles, so
      splitters are distribution-robust; costs a second local sort after
      the exchange (the reference pays a k-way merge there instead,
      :436 — on TPU a re-sort IS the merge, see module docstring)."""
    env = table.env
    by = [by] if isinstance(by, str) else list(by)
    if not by:
        raise InvalidError("sort needs at least one key column")
    from ..obs import plan as _plan
    with _plan.node("sort", by=tuple(by), method=method) as pn:
        if pn:
            pn.set(rows_in=table.row_count, rows_out=table.row_count)
        return _sort_table_impl(table, by, ascending, nulls_position,
                                num_samples, method, pn)


def _sort_table_impl(table: Table, by: list, ascending,
                     nulls_position: str, num_samples: int, method: str,
                     pn) -> Table:
    env = table.env
    from ..obs import plan as _plan
    # hashed-string keys: rewrite to value-stable byte lanes, sort on the
    # lanes, drop them — lexical order on arbitrary-cardinality strings
    expanded = _expand_hashed_string_keys(table, by, ascending)
    if expanded is not None:
        table2, by2, asc2, orig_by = expanded
        out = sort_table(table2, by2, asc2, nulls_position, num_samples,
                         method)
        synth = set(by2) - set(orig_by)
        cols = {n: c for n, c in out.columns.items() if n not in synth}
        res = Table(cols, env, out.valid_counts)
        # lane-tuple equality == value equality (the depth covers every
        # distinct pair's common prefix), so the grouped contract holds
        # for the original keys
        res.grouped_by = tuple(orig_by)
        return res
    descendings = _norm_dirs(by, ascending)
    npos = pack.NULL_FIRST if nulls_position == "first" else pack.NULL_LAST
    by_cols = [table.column(n) for n in by]
    from ..core.dtypes import LogicalType
    for n, c in zip(by, by_cols):
        if c.type == LogicalType.LIST:
            raise InvalidError(
                f"sort on list passthrough column {n!r} is not supported "
                "(codes are row ids, not value-ordered)")
    if method not in ("initial", "regular"):
        raise InvalidError("sort method must be 'initial' or 'regular'")
    w = env.world_size
    if method == "regular" and w > 1 and table.row_count > 0:
        # quantile-exact splitter samples come from the SORTED shards
        table = local_sort_table(table, by, ascending, nulls_position)
        by_cols = [table.column(n) for n in by]
    by_datas, by_valids = col_arrays(by_cols)
    vc = np.asarray(table.valid_counts, np.int32)

    narrow_keys = narrow32_flags(by_cols)
    if w > 1 and table.row_count > 0:
        # ---- range partition by sampled splitters ------------------------
        if num_samples <= 0:
            num_samples = config.sort_samples(w)
        m = min(max(table.capacity, 1), num_samples)
        if pn:
            # profiler piggyback on the splitter sampling path: the same
            # evenly-spaced per-shard positions (common.sample_positions)
            # feed a Misra-Gries key profile (obs/plan), so a skewed sort
            # key is named here before the range exchange concentrates
            # it.  It is a second small device program, not a reuse of
            # _sample_fn's outputs: those are TRANSFORMED sort operands
            # (direction-flipped, null-folded, bias-rebased — pack.
            # key_operands) from which the original key VALUES are not
            # recoverable.  Armed ANALYZE runs only.
            pn.annotate(route="sample_sort", num_samples=m,
                        splitters=w - 1)
            _plan.profile_keys(pn, table, by)
        _SAMPLE_SORTS.inc()
        with timing.region("sort.sample"):
            sample_ops, live = _sample_fn(env.mesh, m, descendings, npos,
                                          narrow_keys)(
                vc, by_datas, by_valids)
            splitters = _pick_splitters(sample_ops, live, w)
        with timing.region("sort.exchange", samples=m) as open_region:
            tgt = _target_fn(env.mesh, descendings, npos, narrow_keys)(
                vc, by_datas, by_valids, splitters)
            counts = shuffle.count_targets(env.mesh, tgt)
            table = exchange_by_targets(table, tgt, counts, owner="sort.recv")
            # how even ``m`` samples a shard made the range partition: the
            # fullest chip's rows, and the capacity every chip sorts at
            timing.set_args(open_region,
                            recv_max=int(table.valid_counts.max()),
                            recv_cap=int(table.capacity))

    # ---- local sort per shard -------------------------------------------
    out = local_sort_table(table, by, ascending, nulls_position)
    # globally sorted by the keys ⇒ equal keys contiguous per shard and
    # (range partition) co-located across shards
    out.grouped_by = tuple(by)
    return out


def local_sort_table(table: Table, by, ascending=True,
                     nulls_position: str = "last",
                     donate: bool = False) -> Table:
    """Per-shard local sort by ``by`` — no exchange: each shard's rows are
    reordered in place (the reference's local ``Sort``,
    arrow_kernels.hpp:121).  Used by :func:`sort_table` after its range
    exchange and by the range-partitioned pipeline (exec/pipeline.py) to
    sort the resident build side ONCE.  Unlike the public sort, hashed
    string keys are allowed here: callers that only need a *consistent*
    total order (range partitioning for equality joins) sort by the codes.

    Column bounds survive (the sort permutes the full padded row set, so
    each column's value multiset is unchanged).

    ``donate=True`` donates the table's column buffers into the sort
    program (docs/pipeline.md donation rules): the caller must own them
    EXCLUSIVELY — no other Table, Column or pending dispatch may alias
    them (the pipelined join donates only its fresh shuffle outputs, and
    only at ``world_size > 1``, where the shuffle guarantees freshness;
    a ``with_columns`` view of a user table shares buffers and must
    never be donated)."""
    env = table.env
    by = [by] if isinstance(by, str) else list(by)
    descendings = _norm_dirs(by, ascending)
    npos = pack.NULL_FIRST if nulls_position == "first" else pack.NULL_LAST
    by_cols = [table.column(n) for n in by]
    vc = np.asarray(table.valid_counts, np.int32)
    items = list(table.columns.items())
    names = [n for n, _ in items]
    # key columns ride inside datas/valids, selected by static position:
    # passing them as separate operands would alias each key buffer into
    # the program twice — unsound under donation (TS108)
    by_idx = tuple(names.index(n) for n in by)
    datas = tuple(c.data for _, c in items)
    valids = tuple(c.validity for _, c in items)
    from .common import table_lane_spec
    narrow = narrow32_flags(by_cols)
    fold = note_liveness("sort", fold_liveness(by_cols,
                                               descending=descendings[0]))
    vspec = table_lane_spec([c for _, c in items])
    f64_idx = tuple(i for i, c in enumerate(vspec.cols) if not c.lanes)
    with timing.region("sort.local"):
        out_d, out_v = _local_sort_fn(env.mesh, descendings, npos, narrow,
                                      vspec, f64_idx, by_idx, donate, fold)(
            vc, datas, valids)
    cols = {}
    for (n, c), d, v in zip(items, out_d, out_v):
        cols[n] = Column(d, c.type, v, c.dictionary, bounds=c.bounds)
    # NOTE: deliberately does NOT set ``grouped_by`` — a per-shard sort
    # only guarantees per-shard contiguity, while grouped_by also asserts
    # cross-shard key co-location (it gates groupby's no-shuffle fast
    # path).  Call sites that additionally guarantee co-location (the
    # range exchange in sort_table, the hash shuffle in pipelined_join)
    # set it themselves.
    return Table(cols, env, table.valid_counts)


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry): the sample-sort
# builders are pure-local shard programs (splitter selection is a
# controller round-trip, the range exchange rides the shuffle engine) —
# the jaxpr pass asserts no hidden collective, no row-scale i32→i64
# widening, zero host callbacks.  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _decl_args(mesh, cap=1024):
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32)
    keys = (S((w * cap,), np.int64),)
    valids = (S((w * cap,), np.bool_),)
    return w, S, vc, keys, valids


def _trace_sample(mesh):
    _w, _S, vc, keys, valids = _decl_args(mesh)
    fn = _unwrap(_sample_fn(mesh, 64, (False,), pack.NULL_LAST, (False,)))
    return jax.make_jaxpr(fn)(vc, keys, valids)


def _trace_target(mesh):
    w, S, vc, keys, valids = _decl_args(mesh)
    sample = _unwrap(_sample_fn(mesh, 64, (False,), pack.NULL_LAST, (False,)))
    sampled, _live = jax.eval_shape(sample, vc, keys, valids)
    splitters = tuple(S((w - 1,), s.dtype) for s in sampled)
    fn = _unwrap(_target_fn(mesh, (False,), pack.NULL_LAST, (False,)))
    return jax.make_jaxpr(fn)(vc, keys, valids, splitters)


def _trace_local_sort(mesh):
    """The phase-1 local sort (ISSUE 6: donation changed its operand
    structure — keys selected from datas by static by_idx so each buffer
    enters the program exactly once, TS108): one nullable int32 lane
    column as the key + one f64 side column gathered at the stable
    permutation.  Pure-local, no collective, no widening."""
    from ..ops import lanes
    w = int(mesh.devices.size)
    cap, S = 1024, jax.ShapeDtypeStruct
    vspec = lanes.plan_lanes(("int32", "float64"), (True, False))
    fn = _unwrap(_local_sort_fn(mesh, (False,), pack.NULL_LAST, (False,),
                                vspec, (1,), (0,)))
    vc = S((w,), np.int32)
    datas = (S((w * cap,), np.int32), S((w * cap,), np.float64))
    valids = (S((w * cap,), np.bool_), None)
    return jax.make_jaxpr(fn)(vc, datas, valids)


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._sample_fn", _trace_sample, tags=("sort",))
declare_builder(f"{__name__}._target_fn", _trace_target, tags=("sort",))
declare_builder(f"{__name__}._local_sort_fn", _trace_local_sort,
                tags=("sort",))
