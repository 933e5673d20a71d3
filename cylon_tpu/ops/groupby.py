"""Segment-reduction groupby kernels.

TPU-native replacement for the reference's groupby engines
(cpp/src/cylon/groupby/hash_groupby.cpp CRTP agg kernels,
cpp/src/cylon/mapreduce/mapreduce.hpp:79 ``MapReduceKernel`` with its
CombineLocally → shuffle intermediates → ReduceShuffledResults → Finalize
flow, and compute/aggregate_kernels.hpp:43 ``AggregationOpId``).

Design: group identity comes from a dense rank (:mod:`.pack`) instead of a
hash map; every aggregation is then a ``jax.ops.segment_*`` — an XLA scatter
that fuses and vectorizes.  The MapReduce decomposition is preserved exactly
because it is what makes distributed groupby cheap: each op declares
*intermediate* columns that are themselves segment-reducible (MEAN →
{sum,count}, VAR/STD → {sum,sumsq,count}), so the distributed path is
local-combine → hash-shuffle intermediates → combine → finalize
(reference groupby/groupby.cpp:33 ``DistributedHashGroupBy``).

Masked (padding) rows are routed to one extra trash segment which is sliced
off — never out-of-bounds scatters.

Supported ops (AggregationOpId parity): sum, count, min, max, mean, var,
std, nunique, quantile/median (+ first/last index helpers).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.stages import stage, staged

#: ops whose intermediates are plain segment reductions (associative —
#: eligible for local pre-combine before the shuffle, groupby.cpp:76-81)
ASSOCIATIVE = {"sum", "count", "min", "max", "mean", "var", "std",
               "sumsq"}
#: ops that must see raw (shuffled) values
NON_ASSOCIATIVE = {"nunique", "quantile", "median"}


def _int_dtype():
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def _route(gids, num_segments, mask):
    """(effective gids, total segments): masked rows → trash segment."""
    if mask is None:
        return gids, num_segments
    return jnp.where(mask, gids, jnp.int32(num_segments)), num_segments + 1


#: below this segment count a dense one-hot masked reduction replaces the
#: scatter: XLA's scatter-add serializes on colliding indices (~72 ns/row
#: measured on v5e at any small segment count, vs ~9-36 ns/row for the
#: dense broadcast-compare-reduce, which the VPU vectorizes across segment
#: lanes; crossover ~8-16k segments)
_DENSE_SEG_MAX = 4096


def _ident(kind: str, dt):
    if kind == "min":
        if jnp.issubdtype(dt, jnp.floating):
            return jnp.asarray(jnp.inf, dt)
        if dt == jnp.bool_:
            return jnp.asarray(True)
        return jnp.asarray(jnp.iinfo(dt).max, dt)
    if kind == "max":
        if jnp.issubdtype(dt, jnp.floating):
            return jnp.asarray(-jnp.inf, dt)
        if dt == jnp.bool_:
            return jnp.asarray(False)
        return jnp.asarray(jnp.iinfo(dt).min, dt)
    return jnp.asarray(0, dt)  # sum


@staged("segment_reduce")
def _seg_apply(kind: str, values, g, ns: int, out_len: int):
    """Segment reduce over ROUTED gids ``g`` (trash segment included in
    ``ns``), returning the first ``out_len`` segments.  Dense one-hot
    reduction below :data:`_DENSE_SEG_MAX`, scatter otherwise — both yield
    the reduction identity for empty segments."""
    if ns <= _DENSE_SEG_MAX:
        eq = g[:, None] == jnp.arange(out_len, dtype=g.dtype)[None, :]
        src = jnp.where(eq, values[:, None], _ident(kind, values.dtype))
        red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[kind]
        return red(src, axis=0)
    fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
          "max": jax.ops.segment_max}[kind]
    return fn(values, g, num_segments=ns)[:out_len]


def seg_sum(values, gids, num_segments, mask=None):
    g, ns = _route(gids, num_segments, mask)
    return _seg_apply("sum", values, g, ns, num_segments)


def seg_count(values, gids, num_segments, mask=None):
    g, ns = _route(gids, num_segments, mask)
    ones = jnp.ones(gids.shape[0], _int_dtype())
    return _seg_apply("sum", ones, g, ns, num_segments)


def seg_min(values, gids, num_segments, mask=None):
    g, ns = _route(gids, num_segments, mask)
    return _seg_apply("min", values, g, ns, num_segments)


def seg_max(values, gids, num_segments, mask=None):
    g, ns = _route(gids, num_segments, mask)
    return _seg_apply("max", values, g, ns, num_segments)


def _ftype(values):
    # accumulate in float64 whenever available: float32 sums over large
    # groups / large-magnitude ints lose precision visibly (and var via
    # E[x^2]-mean^2 compounds it with cancellation)
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


# ---------------------------------------------------------------------------
# Grouped-run reductions (fast path for inputs with contiguous equal keys)
#
# Scatter-add segment reductions dominate groupby runtime on TPU for large
# segment counts.  When the input is already grouped (join/sort output), a
# per-group sum is a difference of the value prefix sum at the run bounds:
# one cumsum + one stacked gather replaces each scatter pass.  Integer
# prefix diffs are exact; float inputs accumulate in float64.
# ---------------------------------------------------------------------------

@staged("segment_starts")
def grouped_starts(first, mask, n_live, seg_cap: int):
    """First live row position of each group id, for grouped input (each
    group one contiguous run in the live prefix).  Slots past the last
    group hold ``n_live`` — making them both the empty-group sentinel and
    the "next start" of the final group, so every run extent is a
    consecutive diff of this one array.

    ONE one-operand sort, no scatter.  Rests on the callers' group ids
    being ``cumsum(first & mask) - 1``: dense and non-decreasing with
    position over the rows where ``first & mask`` holds, each such
    position ``< n_live``.  Then group g starts at the g-th smallest
    start position; every other row carries the fill ``n_live`` and
    sorts behind them, and a ``seg_cap`` under the group count keeps the
    first ``seg_cap`` starts.  Ties are among the fill alone, so the sort
    is NOT stable: stability costs a second (iota) operand on XLA:TPU."""
    n = first.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    srt = jax.lax.sort(jnp.where(first & mask, pos, n_live), is_stable=False)
    if seg_cap <= n:
        return srt[:seg_cap]
    return jnp.concatenate([srt, jnp.full(seg_cap - n, n_live, jnp.int32)])


_GROUPED_NEEDS = {"sum": ("sum",), "count": ("count",),
                  "sumsq": ("sumsq",),
                  "mean": ("sum", "count"),
                  "var": ("sum", "sumsq", "count"),
                  "std": ("sum", "sumsq", "count")}


def _u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


#: XLA:TPU's scan rewriter (``tpu-reduce-window-rewriter``) leaves a scan
#: of at most this many elements as it is
_SCAN_BLOCK = 128


def blocked_cumsum(x):
    """``jnp.cumsum`` of a 1-D array written as scans of at most
    :data:`_SCAN_BLOCK` elements: inside each block of 128, over the
    block totals (recursively), then one add - the two-level form
    XLA:TPU's scan rewriter gives a long scan itself.  For the 64-bit
    scans that are left on a mesh of more than one device - the
    ``pair64`` form of an integer sum (no bounds, or bounds past int32;
    the sums of partial sums in ``_final_fn``) and the float64 prefixes:
    there the rewriter dies (SIGSEGV, a use after free inside the
    compiler, in-process) rewriting the (hi, lo) variadic reduce-windows
    a long 64-bit scan lowers to, once a program holds about four of them
    (described ``v5e:2x2`` compiles, PERF.md PR 28); a scan it does not
    rewrite cannot meet that.  It buys no time: a sum in blocks costs the
    flat pair's 1.05 ns a row (PERF.md §5), which is why a sum whose
    VALUES fit int32 does not come here at all
    (:func:`carried_cumsum32`).  Integer sums are equal bit for bit; a
    float sum is reassociated as the rewriter would."""
    n = x.shape[0]
    if n <= _SCAN_BLOCK:
        return jnp.cumsum(x)
    m = -(-n // _SCAN_BLOCK)
    blocks = jnp.pad(x, (0, m * _SCAN_BLOCK - n)).reshape(m, _SCAN_BLOCK)
    inner = jnp.cumsum(blocks, axis=1)
    total = inner[:, -1]
    before = blocked_cumsum(total) - total     # exclusive, per block
    return (inner + before[:, None]).reshape(-1)[:n]


#: how the prefix of an integer ``sum`` is scanned and how many lanes it
#: rides, by what the host has proven of the value column (decided in
#: relational/groupby.sum_scan_form, from dtype and ``Column.bounds``
#: alone): ``sum32`` - rows * max|v| fits int32, so the SUM does: one int32
#: scan, one lane; ``val32`` - each VALUE fits int32: 32-bit scans
#: (:func:`carried_cumsum32`), the exact int64 prefix in two lanes;
#: ``pair64`` - nothing proven: the int64 scan, two lanes
SUM_FORMS = ("sum32", "val32", "pair64")

#: block sizes of :func:`carried_cumsum32` that beat its flat form by more
#: than 5 ms a sum at the cells' 65M rows on v5e, largest first: 22.1 and
#: 26.2 ms against 34.9 (32 rows: 31.3; 16: 46.0 - the blocks are laid
#: out rows-minor and short ones scan worse; PERF.md PR 40)
VAL32_BLOCKS = (128, 64)


class SumScan(NamedTuple):
    """The static descriptor of one integer ``sum``'s prefix scan: a word
    of :data:`SUM_FORMS` and, for ``val32``, the rows a block of
    :func:`carried_cumsum32` - the largest of :data:`VAL32_BLOCKS` whose
    block sums the bounds prove to fit int32, 1 (flat) where none does.
    What the program caches key on; never the bounds themselves."""
    form: str
    block: int = 1

    def __str__(self):
        return self.form if self.block == 1 else f"{self.form}/{self.block}"


def carried_cumsum32(x, block: int = 1):
    """The exact int64 ``cumsum`` of an int32 array as its two 32-bit
    words ``(hi, lo)``, int32 each - the (hi, lo) u32 lanes of the
    prefix, bit for bit - in int32 scans and no 64-bit operation.

    Flat (``block`` 1): ``lo = cumsum(x)`` wrapping mod 2^32 IS the low
    word; step p carries out of it exactly when the wrapped ``lo[p]`` is
    below ``u32(x[p])``, and a negative x[p] (sign-extended: 2^32 - 1 in
    the high word) borrows one, so ``hi = cumsum(carry - neg)``: two
    row-length scans.  In blocks (the caller has proven ``block *
    max|x|`` to fit int32): an int32 ``cumsum`` inside each block is
    exact, the flat form runs over the N/block block totals alone, and
    ``before[block] + local`` is one 32-bit add with the same carry rule:
    ONE row-length scan.  Integer addition is associative, so however the
    compiler trees the scans the words are those of
    ``cumsum(x.astype(int64))``.

    Why not the int64 scan: XLA:TPU lowers it to a VARIADIC two-operand
    (hi, lo) ``reduce-window`` at 1.05 ns a row - this is 0.34 / 0.40 in
    blocks of 128 / 64 and 0.54 flat (PERF.md PR 40) - every row-length
    64-bit value compiles for a minute at the cells' 65M rows against
    seconds, and on a mesh it is the scan the rewriter faults on
    (:func:`blocked_cumsum`)."""
    def words(t):
        lo = jnp.cumsum(t, dtype=jnp.int32)
        step = (_u32(lo) < _u32(t)).astype(jnp.int32) \
            - (t < 0).astype(jnp.int32)
        return jnp.cumsum(step, dtype=jnp.int32), lo, step

    if block <= 1:
        return words(x)[:2]
    n = x.shape[0]
    m = -(-n // block)
    inner = jnp.cumsum(jnp.pad(x, (0, m * block - n)).reshape(m, block),
                       axis=1, dtype=jnp.int32)
    total = inner[:, -1]
    hi_t, lo_t, step = words(total)
    blo, bhi = (lo_t - total)[:, None], (hi_t - step)[:, None]  # exclusive
    lo = blo + inner                                            # wraps
    hi = bhi + (_u32(lo) < _u32(blo)).astype(jnp.int32) \
        - (inner < 0).astype(jnp.int32)
    return hi.reshape(-1)[:n], lo.reshape(-1)[:n]


def grouped_reduce(ops, values_list, vmasks, starts, n_live, key_datas,
                   key_valids, seg_cap: int, key_narrow=None,
                   sum_forms=None, use_window: int = 0,
                   blocked_scans: bool = False):
    """Grouped-input fast path, fully batched: per-group sums for the
    cumsum-able ops (sum/count/mean/var/std) AND the representative-key
    gather share ONE u32 lane-matrix gather (plus one f64 side gather when
    float accumulators are present — f64 cannot lane-split on TPU).

    For contiguous runs, group g's sum over x is PS[starts[g+1]] -
    PS[starts[g]], with PS the zero-padded exclusive prefix of x and
    starts[n_groups..] = n_live — so a single (seg_cap, L) gather of the
    stacked prefix lanes at ``starts`` + a consecutive diff replaces every
    per-column reduction pass (the gather is ~15 ns a slot through XLA,
    a sixth of that through the windowed kernel; splitting an i64 prefix
    into (hi, lo) u32 lanes is elementwise, but SCANNING it in 64 bits is
    not cheap: 1.05 ns a row a sum, 3-6 x an int32 scan).  Key columns and
    their validity ride the same gather as passthrough lanes;
    ``key_narrow[i]`` (host-known bounds fit int32) rides a 64-bit key as
    ONE lane; ``sum_forms[i]`` (a :class:`SumScan`, or None - a WORD and
    a block size, so compiled-fn caches key on the decision, not on raw
    data bounds) says how the i-th op's integer SUM prefix is scanned:
    ``sum32`` one int32 scan and one lane, ``val32`` the 32-bit scans of
    :func:`carried_cumsum32` and the same two lanes as ``pair64``, which
    is the int64 scan and what None means.  Counts are int32 scans
    whatever it says; float / ``sumsq`` / ``mean`` / ``var`` prefixes do
    not read it.

    ``use_window`` (a window size, 0 = off) routes the u32 matrix gather
    through the Pallas windowed kernel (ops/pallas_gather) — ~6x the XLA
    gather at bench density.  Returns (inter dicts per op, key_out tuple,
    kval_out tuple, win_ok) — win_ok is a scalar bool that is False when
    a windowed tile's index span overflowed (results are then garbage and
    the DISPATCH layer must re-run with use_window=0).

    ``blocked_scans``: the program is compiled for more than one device
    (relational/common.multi_shard), so every 64-bit prefix sum that is
    left (``pair64``, float64) is a :func:`blocked_cumsum`; the 32-bit
    forms hold nothing it guards against."""
    from . import lanes as lanes_mod
    n = key_datas[0].shape[0]

    # entries: (kind, slot, name) with kind prefix|key|kval; each appends
    # its u32 lanes (or f64 side columns) plus a reconstruction recipe
    u32_cols: list = []    # (n+1,) u32 arrays
    f64_cols: list = []    # (n+1,) f64 arrays (side channel)
    recipes: list = []     # (kind, slot, name, space, lane_ids, meta)

    acc_i = _int_dtype()   # int64, or int32 under the CYLON_TPU_X64=0 opt-out

    def prefix_sum(x):
        wide = np.dtype(x.dtype).itemsize == 8
        return blocked_cumsum(x) if blocked_scans and wide else jnp.cumsum(x)

    def prefix_lanes(src, islot, name):
        if jnp.issubdtype(src.dtype, jnp.floating):
            with stage("scan"):
                ps = jnp.concatenate([jnp.zeros(1, src.dtype),
                                      prefix_sum(src)])
            if src.dtype == jnp.float32 and not jax.config.jax_enable_x64:
                u32_cols.append(_u32(ps))
                recipes.append(("prefix", islot, name, "u32",
                                (len(u32_cols) - 1,), "f32"))
            else:
                f64_cols.append(ps.astype(jnp.float64))
                recipes.append(("prefix", islot, name, "f64",
                                (len(f64_cols) - 1,), None))
            return
        form = sum_forms[islot] if name == "sum" and sum_forms else None
        word = form.form if form else "pair64"
        # a count never passes the row count (< 2^31), a ``sum32`` sum
        # never passes int32: the prefix is an int32 scan, one lane, not
        # an (hi, lo) pair narrowed afterwards
        narrow = name == "count" or word == "sum32" \
            or np.dtype(acc_i).itemsize == 4
        acc = jnp.int32 if narrow else acc_i
        if word == "val32" and not narrow:
            # the exact int64 prefix, scanned in 32 bits: its words ARE
            # the two lanes
            with stage("scan"):
                words = [jnp.concatenate([jnp.zeros(1, jnp.int32), w])
                         for w in carried_cumsum32(src.astype(jnp.int32),
                                                   form.block)]
        else:
            with stage("scan"):
                ps = jnp.concatenate([jnp.zeros(1, acc),
                                      prefix_sum(src.astype(acc))])
            words = [ps]
        with stage("pack"):
            # 1 lane narrow, else (hi, lo)
            ls = [l for w in words for l in lanes_mod._to_lanes(w, narrow)]
        u32_cols.extend(ls)
        recipes.append(("prefix", islot, name, "u32",
                        tuple(range(len(u32_cols) - len(ls),
                                    len(u32_cols))),
                        ("int32" if name == "count"
                         or np.dtype(acc_i).itemsize == 4
                         else "int64", narrow)))

    @staged("pack")
    def pass_lanes(src, kind, kslot):
        """Passthrough (gathered at start, no diff): key data / validity.
        Lane split/reconstruct delegates to lanes._to_lanes/_from_lanes
        (one fork of the per-dtype packing rules, not two); recipe meta =
        (dtype name, narrow flag) for the reconstruction."""
        ext = jnp.concatenate([src, src[-1:]])
        dt = np.dtype(ext.dtype)
        if dt == np.float64:
            f64_cols.append(ext)
            recipes.append((kind, kslot, None, "f64",
                            (len(f64_cols) - 1,), ("float64", False)))
            return
        nrw = key_narrow is not None and kind == "key" \
            and bool(key_narrow[kslot]) and dt.itemsize == 8 \
            and dt.kind in ("i", "u")
        if np.issubdtype(dt, np.floating) and dt != np.float32:
            ext = ext.astype(jnp.float32)  # f16 widens; recon casts back
        ls = lanes_mod._to_lanes(ext, nrw)
        u32_cols.extend(ls)
        recipes.append((kind, kslot, None, "u32",
                        tuple(range(len(u32_cols) - len(ls),
                                    len(u32_cols))), (dt.name, nrw)))

    for i, op in enumerate(ops):
        vm = vmasks[i] if vmasks[i] is not None else jnp.ones(n, bool)
        v = values_list[i]
        f = v.astype(_ftype(v)) if (op in ("mean", "var", "std", "sumsq")
                                    or jnp.issubdtype(v.dtype, jnp.floating)) \
            else v
        for name in _GROUPED_NEEDS[op]:
            if name == "count":
                src = vm.astype(jnp.int32)
            elif name == "sum":
                src = jnp.where(vm, f, jnp.zeros_like(f))
            else:
                src = jnp.where(vm, f * f, jnp.zeros_like(f))
            prefix_lanes(src, i, name)
    for ki, (d, v) in enumerate(zip(key_datas, key_valids)):
        pass_lanes(d, "key", ki)
        if v is not None:
            pass_lanes(v, "kval", ki)

    @staged("segment_gather")
    def gather_pair(cols):
        mat = jnp.stack(cols, axis=1)                  # (n+1, L)
        g = mat[starts]                                # THE gather
        # "next start" of slot seg_cap-1 is n_live (PS there = full total)
        tailv = mat[jnp.minimum(n_live, n)][None, :]
        g_next = jnp.concatenate([g[1:], tailv], axis=0)
        return g, g_next

    win_ok = jnp.ones((), bool)
    windowed = False
    if use_window and u32_cols:
        from . import pallas_gather as pg
        windowed = pg.supported(n + 1, seg_cap, len(u32_cols), use_window)
    g_u = gn_u = g_f = gn_f = None
    if windowed:
        # lane-major stack (a post-hoc transpose would cost ~700 ms; the
        # axis-0 stack is a plain concat); f64 side columns keep the XLA
        # gather below
        with stage("segment_gather"):
            mat_t = jnp.stack(u32_cols, axis=0)
            g_u, win_ok = pg.windowed_take_t(mat_t, starts, use_window)
            tail = jax.lax.dynamic_slice(
                mat_t, (jnp.int32(0), jnp.minimum(n_live, jnp.int32(n))),
                (len(u32_cols), 1))
            gn_u = jnp.concatenate([g_u[:, 1:], tail], axis=1)
    elif u32_cols:
        g_u, gn_u = gather_pair(u32_cols)
    if f64_cols:
        g_f, gn_f = gather_pair(f64_cols)

    def ucol(li, at_next: bool):
        src = gn_u if at_next else g_u
        return src[li] if windowed else src[:, li]

    @staged("unpack")
    def prefix_recon(lane_ids, meta, at_next: bool):
        """Gathered prefix lanes -> accumulator value (i32/i64/f32/f64)."""
        if meta is None:  # f64 side channel
            return (gn_f if at_next else g_f)[:, lane_ids[0]]
        if meta == "f32":
            return jax.lax.bitcast_convert_type(ucol(lane_ids[0], at_next),
                                                jnp.float32)
        dt_name, nrw = meta
        return lanes_mod._from_lanes([ucol(li, at_next) for li in lane_ids],
                                     dt_name, nrw)

    inters = [dict() for _ in ops]
    key_out = [None] * len(key_datas)
    kval_out = [None] * len(key_datas)
    for kind, slot, name, space, lane_ids, meta in recipes:
        if kind == "prefix":
            hi = prefix_recon(lane_ids, meta, True)
            lo = prefix_recon(lane_ids, meta, False)
            with stage("segment_reduce"):
                d = hi - lo
                if name == "count":
                    d = d.astype(_int_dtype())
            inters[slot][name] = d
        else:
            dt_name, nrw = meta
            if space == "f64":
                v = g_f[:, lane_ids[0]]
            else:
                with stage("unpack"):
                    v = lanes_mod._from_lanes([ucol(li, False)
                                               for li in lane_ids],
                                              dt_name, nrw)
            if kind == "key":
                key_out[slot] = v
            else:  # validity lanes are always planned as bool
                kval_out[slot] = v
    return inters, tuple(key_out), tuple(kval_out), win_ok


#: ops whose grouped-input fast path avoids scatter reductions entirely
CUMSUMMABLE = {"sum", "count", "mean", "var", "std", "sumsq"}


# ---------------------------------------------------------------------------
# MapReduce decomposition (reference mapreduce.hpp:56-76 six-stage flow)
# ---------------------------------------------------------------------------

def combine_locally(op: str, values, gids, num_segments, mask=None):
    """Stage 1: per-group intermediates on local rows.  Returns a dict of
    named intermediate arrays, each of length num_segments; sum, sumsq
    and count reduce further by summing, min and max by min and max."""
    if op == "sum":
        return {"sum": seg_sum(values, gids, num_segments, mask)}
    if op == "count":
        return {"count": seg_count(values, gids, num_segments, mask)}
    if op == "min":
        return {"min": seg_min(values, gids, num_segments, mask),
                "count": seg_count(values, gids, num_segments, mask)}
    if op == "max":
        return {"max": seg_max(values, gids, num_segments, mask),
                "count": seg_count(values, gids, num_segments, mask)}
    if op == "mean":
        f = values.astype(_ftype(values))
        return {"sum": seg_sum(f, gids, num_segments, mask),
                "count": seg_count(values, gids, num_segments, mask)}
    if op in ("var", "std"):
        f = values.astype(_ftype(values))
        return {"sum": seg_sum(f, gids, num_segments, mask),
                "sumsq": seg_sum(f * f, gids, num_segments, mask),
                "count": seg_count(values, gids, num_segments, mask)}
    if op == "sumsq":
        f = values.astype(_ftype(values))
        return {"sumsq": seg_sum(f * f, gids, num_segments, mask)}
    raise ValueError(f"op {op} has no associative decomposition")


@staged("segment_reduce")
def finalize(op: str, inter: dict, ddof: int = 1):
    """Stage 5: intermediates → (result_values, result_validity|None)."""
    cnt = inter.get("count")
    if op == "sum":
        return inter["sum"], None
    if op == "sumsq":
        return inter["sumsq"], None
    if op == "count":
        return inter["count"], None
    if op == "min":
        return inter["min"], (cnt > 0) if cnt is not None else None
    if op == "max":
        return inter["max"], (cnt > 0) if cnt is not None else None
    if op == "mean":
        c = jnp.maximum(cnt, 1).astype(inter["sum"].dtype)
        return inter["sum"] / c, cnt > 0
    if op in ("var", "std"):
        c = jnp.maximum(cnt, 1).astype(inter["sum"].dtype)
        mean = inter["sum"] / c
        var = jnp.maximum(inter["sumsq"] / c - mean * mean, 0.0)
        denom = jnp.maximum(cnt - ddof, 1).astype(var.dtype)
        var = var * (c / denom)
        ok = cnt > ddof
        return (jnp.sqrt(var) if op == "std" else var), ok
    raise ValueError(f"unknown associative op {op}")


# ---------------------------------------------------------------------------
# Non-associative ops on raw (possibly shuffled) values
# ---------------------------------------------------------------------------

def nunique(value_keyops, gids, num_segments, mask=None):
    """Distinct count per group: sort (gid, value...) tuples, count boundary
    transitions per segment.  ``value_keyops`` is a
    :class:`~cylon_tpu.ops.pack.KeyOps` over the value column; pass a mask to
    exclude padding/null rows (pandas nunique drops nulls)."""
    from .pack import neighbor_flags
    g, ns = _route(gids, num_segments, mask)
    keys = (g,) + value_keyops.ops
    kinds = ("i",) + value_keyops.kinds
    with stage("sort_keys"):
        srt = jax.lax.sort(keys, num_keys=len(keys), is_stable=False)
    gs = srt[0]
    first = jnp.concatenate([jnp.ones(1, jnp.int32),
                             jnp.zeros(gs.shape[0] - 1, jnp.int32)]) \
        if gs.shape[0] else jnp.zeros(0, jnp.int32)
    neq = neighbor_flags(srt, kinds) | first
    return _seg_apply("sum", neq, gs, ns, num_segments)


def quantile(values, gids, num_segments, q: float, mask=None):
    """Per-group quantile with linear interpolation.  Sorts (gid, value) then
    indexes each group's sorted run via count prefix sums."""
    f = values.astype(_ftype(values))
    g, ns = _route(gids, num_segments, mask)
    v = f if mask is None else jnp.where(mask, f, jnp.inf)
    with stage("sort_keys"):
        g_s, v_s = jax.lax.sort((g, v), num_keys=2, is_stable=False)
    cnt_all = _seg_apply("sum", jnp.ones_like(g, dtype=_int_dtype()), g,
                         ns, ns)
    offs_all = jnp.concatenate(
        [jnp.zeros(1, cnt_all.dtype), jnp.cumsum(cnt_all)[:-1]])
    cnt, offs = cnt_all[:num_segments], offs_all[:num_segments]
    posf = jnp.asarray(q, f.dtype) * jnp.maximum(cnt - 1, 0).astype(f.dtype)
    lo = jnp.floor(posf).astype(cnt.dtype)
    hi = jnp.ceil(posf).astype(cnt.dtype)
    frac = posf - lo.astype(f.dtype)
    n = v_s.shape[0]
    take = lambda i: v_s[jnp.clip(offs + i, 0, max(n - 1, 0)).astype(jnp.int32)]
    vlo, vhi = take(lo), take(hi)
    return vlo + (vhi - vlo) * frac, cnt > 0


def group_first_index(gids, num_segments, mask=None):
    """Representative (first) source-row index per group — used to gather the
    key columns of the groupby result."""
    n = gids.shape[0]
    g, ns = _route(gids, num_segments, mask)
    idx = jnp.arange(n, dtype=jnp.int32)
    return _seg_apply("min", idx, g, ns, num_segments)


def np_result_dtype(op: str, src: np.dtype) -> np.dtype:
    if op in ("count", "nunique"):
        return np.dtype(np.int64)
    if op in ("mean", "var", "std", "sumsq", "quantile", "median"):
        # float32 in -> float32 out (pandas parity); everything else f64.
        # Accumulation happens in _ftype regardless; this is the result cast.
        return (np.dtype(np.float32) if src == np.dtype(np.float32)
                else np.dtype(np.float64))
    return np.dtype(src)


# ---------------------------------------------------------------------------
# armed-audit saturation guard (the integrity tier's abort-not-wrong
# satellite — docs/robustness.md "Integrity audit tier")
# ---------------------------------------------------------------------------

#: int64 accumulators past this magnitude count as saturated: 2**62
#: leaves headroom for ONE more combine doubling, so the guard fires
#: while the value is still meaningful — both pre-wrap (a huge positive
#: one step from wrapping) and post-wrap (the wrapped negative) land
#: outside the rail.  The ±rail form also avoids the int64 abs(INT64_MIN)
#: trap (abs of the minimum is itself negative).
SATURATION_RAIL = 1 << 62


def guard_saturation(op: str, data, *, column=None,
                     site: str = "groupby.finalize") -> None:
    """Armed-audit overflow guard (``CYLON_TPU_AUDIT=1``): int64
    ``sum``/``count`` accumulators wrap silently in XLA — a saturated
    aggregate is a WRONG answer, not an error.  Called at the host
    assembly boundary (concrete result columns, never inside a traced
    builder); raises a typed
    :class:`~cylon_tpu.status.NumericOverflowError` so the run aborts
    instead of publishing the wrap.  Unarmed: one env-cached load."""
    from ..exec import integrity
    if not integrity.armed():
        return
    if op not in ("sum", "count"):
        return
    if np.dtype(getattr(data, "dtype", "f8")) != np.dtype(np.int64):
        return
    if not getattr(data, "size", 0):
        return
    hi, lo = int(jnp.max(data)), int(jnp.min(data))
    if hi > SATURATION_RAIL or lo < -SATURATION_RAIL:
        from ..status import NumericOverflowError
        raise NumericOverflowError(
            f"groupby {op} accumulator saturated int64 (|value| > 2**62; "
            f"max={hi}, min={lo}): the aggregate has wrapped or is one "
            "combine away from wrapping — aborting instead of returning "
            "a silently wrong answer", site=site, column=column)
