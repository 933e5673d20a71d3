"""Shared plumbing for the table-level operators.

Mirrors the role of the reference's util layer for its Table ops
(cpp/src/cylon/util/arrow_utils.hpp, join/join_utils.hpp output assembly,
partition/partition.hpp): key-column canonicalization, string-dictionary
unification across tables (the reference compares strings via dual-table
comparators, arrow_comparator.hpp:238 — here both sides must share one code
space), per-shard liveness masks, and result-table assembly.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.column import Column
from ..core.dtypes import LogicalType, physical_np_dtype
from ..core.table import Table
from ..ctx.context import ROW_AXIS, CylonEnv
from ..obs import metrics as _metrics
from ..ops import pack
from ..ops.pack import PAD_L, PAD_R  # noqa: F401 (the tables' pad keys)
from ..status import CylonTypeError, InvalidError
from ..utils.stages import staged

ROW = P(ROW_AXIS)
REP = P()


class BoundedCache(dict):
    """Bounded FIFO mapping for callsite -> capacity-prediction caches
    (join output caps, groupby segment caps): oldest entry evicted at
    ``maxlen`` so varying input shapes cannot grow it without limit."""

    def __init__(self, maxlen: int = 512):
        super().__init__()
        self.maxlen = maxlen

    def put(self, key, value) -> None:
        if key not in self and len(self) >= self.maxlen:
            self.pop(next(iter(self)))
        self[key] = value


def is_oom(e: Exception) -> bool:
    """Device out-of-memory, as surfaced by XLA/PJRT.  Delegates to the
    fault-taxonomy boundary (exec/recovery — the ONE sanctioned place that
    string-matches runtime OOM text, lint rule TS105)."""
    from ..exec.recovery import is_oom as _is_oom
    return _is_oom(e)


def run_with_oom_fallback(primary, can_fallback: bool, fallback, label: str,
                          env=None):
    """``primary()`` with chunked-streaming capacity retries, routed
    through the rank-coherent consensus ladder
    (exec/recovery.run_with_recovery): faults are classified onto the
    typed taxonomy, multiprocess sessions agree on ONE status code before
    any retry/abort branch, and escalation is bounded and deterministic
    (OOM: ``fallback(4)`` then ``fallback(16)``; capacity overflow: one
    cap-halving step).  Non-fault errors always propagate.  Shared by
    join_tables, groupby_aggregate and set_operation — one retry policy,
    one coherence protocol.  Pass ``env`` so multiprocess sessions can
    run the consensus all-reduce over its mesh."""
    from ..exec.recovery import run_with_recovery
    return run_with_recovery(primary, can_fallback, fallback, label, env=env)


def sample_positions(n, m: int, cap: int) -> jax.Array:
    """m evenly spaced in-range row positions over a live prefix of traced
    length ``n`` (float stride: arange(m)*n would overflow int32 under
    x64=0).  Shared by sort splitter sampling and skew-key sampling."""
    stride = jnp.maximum(n, 1).astype(jnp.float32) / m
    idx = (jnp.arange(m, dtype=jnp.float32) * stride).astype(jnp.int32)
    return jnp.clip(idx, 0, cap - 1)


@staged("liveness")
def live_mask(vc: jax.Array, cap: int) -> jax.Array:
    """Per-shard row-liveness mask (call inside shard_map): the first
    ``vc[my_rank]`` rows of the shard are real, the rest padding."""
    my = jax.lax.axis_index(ROW_AXIS)
    return jnp.arange(cap) < vc[my]


@staged("liveness")
def live_count(vcl: jax.Array, vcr: jax.Array) -> jax.Array:
    """Live rows of this shard's (left ++ right) concat as an int32 scalar
    (call inside shard_map): the length of a sorted join state's live
    prefix (ops/join.join_sort_state's invariant)."""
    my = jax.lax.axis_index(ROW_AXIS)
    return (vcl[my] + vcr[my]).astype(jnp.int32)


def multi_shard() -> bool:
    """Whether the program being traced is compiled for more than one
    device (call inside shard_map; static): what
    ``ops/groupby.grouped_reduce`` needs to know to keep a long 64-bit
    scan away from XLA:TPU's scan rewriter (``blocked_scans``)."""
    return jax.lax.axis_size(ROW_AXIS) > 1


def valid_flag(col: Column):
    """Boolean filter payload of a bool column with null rows forced False
    (pandas/Arrow semantics: a null predicate never selects a row).  Every
    filter-on-bool-column call site must go through this."""
    flag = col.data
    if col.validity is not None:
        flag = flag & col.validity
    return flag


def fits_int32(c: Column) -> bool:
    """Host-known: this 64-bit integer column's value bounds fit int32, so
    any lane/operand packing may use one native 32-bit lane instead of a
    (hi, lo) pair.  Non-64-bit columns return False (already native)."""
    if c.data.dtype.itemsize != 8 or c.data.dtype.kind not in ("i", "u"):
        return False
    return c.bounds is not None and c.bounds[0] >= -(1 << 31) \
        and c.bounds[1] <= (1 << 31) - 1


def narrow32_flags(*col_lists) -> tuple:
    """Static per-key-column flags: True when every listed column's
    host-known bounds fit int32 (:func:`fits_int32`), so sort-operand
    packing may use one native operand instead of a (hi, lo) pair.  Pass
    the aligned key columns of all tables that will be ranked together."""
    n = len(col_lists[0])
    return tuple(all(fits_int32(cl[i]) for cl in col_lists)
                 for i in range(n))


def key_bounds(type, dictionary, bounds):  # noqa: A002
    """Host-known ``(lo, hi)`` of a key column's physical values, from its
    ``Column.type`` / ``.dictionary`` / ``.bounds`` (or a packed piece's
    metadata): the bounds, or what a dictionary-coded string column's
    sorted dictionary proves of its int32 codes (``[0, len)``; hashed
    codes use int64's width and prove nothing)."""
    if bounds is None and type == LogicalType.STRING \
            and isinstance(dictionary, np.ndarray):
        return (0, max(len(dictionary) - 1, 0))
    return bounds


#: how a key sort that has padding to keep behind its live rows did it, one
#: count a dispatched sort, by the operator that asked: ``folded`` into the
#: leading key operand (one operand fewer), a liveness ``operand`` of its
#: own (the leading key has no room: :func:`fold_liveness`), or ``all_live``
#: (tables at capacity: no padding, neither).  Registered at import so that
#: a snapshot shows the whole family.
_KEY_SORT_LIVENESS = {
    (form, site): _metrics.counter("key_sort_liveness", form=form, site=site)
    for form in ("folded", "operand", "all_live")
    for site in ("join", "setops", "groupby", "sort")}


def note_liveness(site: str, fold: bool, all_live: bool = False) -> bool:
    """Count one key sort of ``site`` under the form its liveness takes;
    returns ``fold`` as the builders' static: False where ``all_live`` (no
    ``row_mask`` reaches the packer, so it would only split a cache)."""
    fold = bool(fold) and not all_live
    _KEY_SORT_LIVENESS["all_live" if all_live else
                       "folded" if fold else "operand", site].inc()
    return fold


def fold_liveness(*col_lists, descending: bool = False) -> bool:
    """Static: may row liveness ride INSIDE the leading key operand of the
    sort that ranks these tables (``pack.key_operands(fold=...)``), so that
    no liveness operand is built?  A sibling of :func:`narrow32_flags`
    (pass the aligned key columns of all tables ranked together, and the
    first key's ``descending``); the rule itself is ``ops/pack.fold_room``
    on the FIRST key column's dtype, null flag and :func:`key_bounds` -
    what the code can observe in its input, no knob.  A key with no room
    keeps the operand, and that program is what it was."""
    firsts = [cl[0] for cl in col_lists]
    return pack.fold_room(firsts[0].data.dtype,
                          any(c.validity is not None for c in firsts),
                          [key_bounds(c.type, c.dictionary, c.bounds)
                           for c in firsts], descending)


def table_lane_spec(cols: list[Column]):
    """LaneSpec over a table's full column list (bounds-narrowed) — the
    static half of moving whole rows with ONE lane-matrix gather
    (ops/lanes.gather_columns) instead of one gather per column."""
    from ..ops import lanes
    return lanes.plan_lanes(tuple(str(c.data.dtype) for c in cols),
                            tuple(c.validity is not None for c in cols),
                            narrow32_flags(cols))


def col_arrays(cols: list[Column]):
    """Split columns into parallel (datas, valids) tuples; valids entries may
    be None (all-valid) — None is an empty pytree so it passes through jit."""
    return tuple(c.data for c in cols), tuple(c.validity for c in cols)


@functools.lru_cache(maxsize=2)
def all_valid(cap: int) -> np.ndarray:
    """The all-true validity stand-in of ``cap`` rows for a key column that
    has none, built once per capacity and kept (read-only).  A fresh
    ``np.ones(cap, bool)`` on every distributed join is a 32 MiB block at
    2^25 rows, which glibc serves from heap it kept (~2.4 ms) or from
    fresh ``mmap`` pages (~33 ms of page faults) by the process's
    allocation history, not its seed: the four-chip cells' second speed
    (PERF.md §6, PR 39)."""
    ones = np.ones(cap, bool)
    ones.setflags(write=False)
    return ones


def promote_key_pair(a: Column, b: Column) -> tuple[Column, Column]:
    """Make a cross-table key pair comparable: unify string dictionaries,
    rescale decimals to a common scale, or promote numerics to a common
    logical type (the reference requires type-equal join keys; we
    additionally auto-promote numerics)."""
    if LogicalType.LIST in (a.type, b.type):
        raise CylonTypeError(
            "list passthrough columns cannot be keys (codes are row ids, "
            "not value-equal); they carry through joins as payload only")
    if (a.type == LogicalType.STRING) != (b.type == LogicalType.STRING):
        raise CylonTypeError(f"cannot join {a.type} with {b.type}")
    if a.type == LogicalType.STRING:
        return unify_dictionaries(a, b)
    if (a.type == LogicalType.DECIMAL) != (b.type == LogicalType.DECIMAL):
        raise CylonTypeError(
            f"cannot join {a.type} with {b.type}; rescale explicitly")
    if a.type == LogicalType.DECIMAL:
        return rescale_decimal_pair(a, b)
    if a.type == b.type:
        return a, b
    common = np.promote_types(physical_np_dtype(a.type), physical_np_dtype(b.type))
    lt = LogicalType(common.name) if common.name in LogicalType._value2member_map_ \
        else None
    if lt is None:
        raise CylonTypeError(f"no common key type for {a.type}/{b.type}")
    return a.cast(lt), b.cast(lt)


def rescale_decimal_pair(a: Column, b: Column) -> tuple[Column, Column]:
    """Bring two DECIMAL columns to one scale (the larger): the scaled
    ints then compare/join exactly.  10^Δ rescale is exact while the
    values stay within the representation's precision bound."""
    a, b = rescale_decimals_many([a, b])
    return a, b


def rescale_decimals_many(cs: list[Column]) -> list[Column]:
    """Bring N DECIMAL columns to ONE common scale in a single pass.
    The shared target is the largest scale, with precision covering EVERY
    column's 10^Δ-scaled digits (a coalesced outer-join key may hold any
    side's values under one declared type).  Past the representation's
    digit bound DecimalScale raises the clear error.

    One pass matters: pairwise promotion of [s=1, s=1, s=4] rescales only
    the columns it touches last, leaving earlier middles at a stale scale
    while the batch takes the final dictionary — a silent value corruption
    because decimals share int64 storage."""
    from ..core.column import DecimalScale
    scales = [c.dictionary for c in cs]
    if all(s == scales[0] for s in scales[1:]):
        return list(cs)
    scale = max(s.scale for s in scales)
    target = DecimalScale(max(s.precision + scale - s.scale for s in scales),
                          scale)

    def up(c: Column, own: DecimalScale) -> Column:
        f = 10 ** (scale - own.scale)
        bounds = ((c.bounds[0] * f, c.bounds[1] * f)
                  if c.bounds is not None else None)
        # python-int multiplier: jax weak typing keeps the data's dtype
        return Column(c.data * f if f != 1 else c.data, LogicalType.DECIMAL,
                      c.validity, target, bounds=bounds)

    return [up(c, s) for c, s in zip(cs, scales)]


def to_hashed_strings(c: Column) -> Column:
    """Re-code a sorted-dictionary string column into hashed-codes space
    (codes = stable 64-bit value hashes; core.column.HashedStrings) so it
    can meet a high-cardinality hashed column in a join/set op."""
    from ..core.column import HashedStrings
    if isinstance(c.dictionary, HashedStrings):
        return c
    from .. import native
    vals = np.asarray(c.dictionary, dtype=object)
    hashes = native.hash_strings(vals) if len(vals) \
        else np.zeros(0, np.uint64)
    remap = hashes.view(np.int64)
    data = jnp.take(jnp.asarray(remap),
                    jnp.clip(c.data, 0, max(len(vals) - 1, 0))) \
        if len(vals) else jnp.zeros_like(c.data, jnp.int64)
    return Column(data, LogicalType.STRING, c.validity,
                  HashedStrings(hashes, vals))


def unify_dictionaries(a: Column, b: Column) -> tuple[Column, Column]:
    """Re-code two dictionary-encoded string columns into one shared sorted
    dictionary (codes stay order-isomorphic to the strings, so sorts/joins on
    codes remain exact).  When either side is hashed (HashedStrings), both
    land in hashed-codes space — codes are globally comparable by
    construction (one hash function), only the decode lookups merge."""
    from ..core.column import HashedStrings
    if isinstance(a.dictionary, HashedStrings) \
            or isinstance(b.dictionary, HashedStrings):
        ah, bh = to_hashed_strings(a), to_hashed_strings(b)
        merged = ah.dictionary.merged_with(bh.dictionary)
        return (Column(ah.data, LogicalType.STRING, ah.validity, merged),
                Column(bh.data, LogicalType.STRING, bh.validity, merged))
    if a.dictionary is b.dictionary or (
            len(a.dictionary) == len(b.dictionary)
            and np.array_equal(a.dictionary, b.dictionary)):
        return a, b
    merged = np.unique(np.concatenate([a.dictionary, b.dictionary]))
    # recode maps stay numpy; jnp.take anchored on the committed codes runs
    # on the codes' device (no default-backend array creation)
    map_a = np.searchsorted(merged, a.dictionary).astype(np.int32)
    map_b = np.searchsorted(merged, b.dictionary).astype(np.int32)
    ca = Column(jnp.take(map_a, jnp.clip(a.data, 0, len(a.dictionary) - 1)),
                LogicalType.STRING, a.validity, merged)
    cb = Column(jnp.take(map_b, jnp.clip(b.data, 0, len(b.dictionary) - 1)),
                LogicalType.STRING, b.validity, merged)
    return ca, cb


def unify_dictionaries_many(cols: list[Column]) -> list[Column]:
    """N-way dictionary unification (used by concat / n-way set ops)."""
    from ..core.column import HashedStrings
    if any(isinstance(c.dictionary, HashedStrings) for c in cols):
        hashed = [to_hashed_strings(c) for c in cols]
        merged = hashed[0].dictionary
        for h in hashed[1:]:
            merged = merged.merged_with(h.dictionary)
        return [Column(h.data, LogicalType.STRING, h.validity, merged)
                for h in hashed]
    dicts = [c.dictionary for c in cols]
    if all(d is dicts[0] or np.array_equal(d, dicts[0]) for d in dicts[1:]):
        return list(cols)
    merged = np.unique(np.concatenate(dicts))
    out = []
    for c in cols:
        m = np.searchsorted(merged, c.dictionary).astype(np.int32)
        out.append(Column(jnp.take(m, jnp.clip(c.data, 0, len(c.dictionary) - 1)),
                          LogicalType.STRING, c.validity, merged))
    return out


def build_table(names, out_datas, out_valids, types, dicts,
                valid_counts: np.ndarray, env: CylonEnv,
                bounds=None) -> Table:
    """Assemble an output Table from kernel results (the static-shape analog
    of the reference's join_utils output builders).  ``bounds`` (optional,
    parallel to names) propagates host-known integer value bounds so
    downstream ops keep their narrow-lane fast paths."""
    cols = {}
    for i, (name, d, v, t, dc) in enumerate(
            zip(names, out_datas, out_valids, types, dicts)):
        b = bounds[i] if bounds is not None else None
        cols[name] = Column(d, t, v, dc, bounds=b)
    return Table(cols, env, np.asarray(valid_counts, np.int64))


def rebuild_like(items, out_datas, out_valids, valid_counts,
                 env: CylonEnv) -> Table:
    """build_table with schema (name/type/dictionary) taken from existing
    (name, Column) pairs — for ops that permute/filter rows of one table."""
    names = [n for n, _ in items]
    types = [c.type for _, c in items]
    dicts = [c.dictionary for _, c in items]
    return build_table(names, out_datas, out_valids, types, dicts,
                       valid_counts, env)


def check_same_env(a: Table, b: Table) -> CylonEnv:
    if a.env is not b.env and a.env.mesh is not b.env.mesh:
        raise InvalidError("tables belong to different CylonEnvs")
    return a.env


# ---------------------------------------------------------------------------
# key-value sampling for the heavy-hitter profiler (obs/plan, obs/sketch)
# ---------------------------------------------------------------------------

from ..utils.cache import jit, program_cache  # noqa: E402


@program_cache()
def _key_sample_fn(mesh, m: int, nkeys: int, with_valids: bool = False):
    """Evenly spaced per-shard sample of RAW key values plus the
    canonicalizing row hash — the sort-splitter sampling machinery
    (:func:`sample_positions`, relational/sort._sample_fn) applied to
    the profiler's needs: values NAME the hot keys (single integer-ish
    keys), the hash covers multi-column/float/string tuples with exactly
    the shuffle-routing predicate (ops/hashing.hash_rows).
    ``with_valids=True`` (the skew-split plan facade, relational/skew.py)
    additionally samples each key column's VALIDITY bit so a sampled
    tuple carries its full null structure — heavy NULL keys participate
    in the split exactly like values.  Pure-local per-shard program: no
    collective, no widening (jaxpr-gate registered)."""
    from ..ops import hashing

    def per_shard(vc, *args):
        datas = list(args[:nkeys])
        valids = list(args[nkeys:])
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        n = vc[my]
        h = hashing.hash_rows(datas, valids)
        idx = sample_positions(n, m, cap)
        live = jnp.full((m,), n > 0)
        out = tuple(d[idx] for d in datas)
        if with_valids:
            out += tuple(v[idx] for v in valids)
        return out + (h[idx], live)

    specs = (REP,) + (ROW,) * (2 * nkeys)
    nouts = nkeys * (2 if with_valids else 1) + 2
    return jit(jax.shard_map(per_shard, mesh=mesh, in_specs=specs,
                                 out_specs=(ROW,) * nouts))


def _key_value_repr(col: Column, vals: np.ndarray):
    """Host-side naming of sampled key values: raw numerics pass
    through; sorted-dictionary string codes decode to their strings;
    hashed-string codes stay codes (stable but opaque)."""
    if col.type == LogicalType.STRING:
        d = col.dictionary
        if isinstance(d, np.ndarray) and len(d):
            return d[np.clip(vals.astype(np.int64), 0, len(d) - 1)]
        # hashed-string codes (HashedStrings) fall through: stable but
        # opaque identities — decoding would need the value store lookup
    return vals


def sample_keys(table: Table, key_names: list, m: int | None = None,
                with_hashes: bool = False):
    """Sample ``table``'s key columns for the heavy-hitter profiler:
    returns ``(values, weights, total_rows)`` — a flat host array of
    sampled key identities (values for a single key column, row hashes
    for composite keys), a parallel weight array normalizing each
    shard's samples by its true row share (the join skew detector's
    weighting, relational/join._heavy_keys), and the global live row
    count.  ``with_hashes=True`` appends a fourth element: the routing
    hash (ops/hashing.hash_rows) aligned with ``values``, so the
    profiler can place each identity on its CURRENT partition
    (obs/plan.key_profile ``est_rows_per_rank``).  None for empty
    tables.  Armed-profiler path only: one small device program + one
    host pull."""
    from .. import config
    from ..utils.host import host_array

    env = table.env
    total = int(table.valid_counts.sum())
    if total == 0:
        return None
    w = env.world_size
    if m is None:
        m = config.SKEW_SAMPLE
    m = min(max(int(table.capacity), 1), int(m))
    cols = [table.column(n) for n in key_names]
    cap = cols[0].data.shape[0]
    datas = tuple(c.data for c in cols)
    valids = tuple(c.validity if c.validity is not None
                   else all_valid(cap) for c in cols)
    outs = _key_sample_fn(env.mesh, m, len(cols))(
        np.asarray(table.valid_counts, np.int32), *datas, *valids)
    vals0 = host_array(outs[0]).reshape(w, m)
    hashes = host_array(outs[-2]).reshape(w, m)
    live = host_array(outs[-1]).reshape(w, m)
    if len(cols) == 1:
        raw = np.asarray(_key_value_repr(cols[0], vals0))
    else:
        raw = hashes
    vc = np.asarray(table.valid_counts, np.float64)
    values, weights, hlist = [], [], []
    for s in range(w):
        lv = raw[s][live[s]]
        if lv.size == 0:
            continue
        values.append(lv)
        hlist.append(hashes[s][live[s]])
        # each shard contributes its true row share, split evenly over
        # its samples — unweighted pooling would let a tiny shard's
        # keys dominate the estimate
        weights.append(np.full(lv.size, vc[s] / total / lv.size))
    if not values:
        return None
    out = (np.concatenate(values), np.concatenate(weights) * total, total)
    if with_hashes:
        out += (np.concatenate(hlist).astype(np.uint32),)
    return out


def sample_key_rows(table: Table, key_names: list, m: int | None = None):
    """Shard-weighted sample of FULL key tuples for the skew-split plan
    facade (relational/skew.py): returns ``(values, valids, hashes,
    weights, total_rows)`` — ``values``/``valids`` are per-key-column
    host arrays of the sampled raw data and validity bits (so a heavy
    tuple can be re-uploaded as an operand-space constant, nulls
    included), ``hashes`` the canonicalizing routing hash per sampled
    row, ``weights`` the same per-shard row-share normalization as
    :func:`sample_keys`.  None for empty tables.  One small pure-local
    device program + one host pull — no collective (the plan decision
    stays rank-uniform because the pull allgathers)."""
    from .. import config
    from ..utils import timing
    from ..utils.host import host_array

    env = table.env
    total = int(table.valid_counts.sum())
    if total == 0:
        return None
    w = env.world_size
    if m is None:
        m = config.SKEW_SAMPLE
    m = min(max(int(table.capacity), 1), int(m))
    # host.skew_operands: the sampler's operands, built on the host - a key
    # column with no validity gets an all-true stand-in of the column's
    # whole capacity (one byte a row; :func:`all_valid` keeps it)
    with timing.span("host.skew_operands"):
        cols = [table.column(n) for n in key_names]
        cap = cols[0].data.shape[0]
        nk = len(cols)
        datas = tuple(c.data for c in cols)
        valids = tuple(c.validity if c.validity is not None
                       else all_valid(cap) for c in cols)
        sample = _key_sample_fn(env.mesh, m, nk, True)
        vc32 = np.asarray(table.valid_counts, np.int32)
    outs = sample(vc32, *datas, *valids)
    vals = [host_array(o).reshape(w * m) for o in outs[:nk]]
    vls = [host_array(o).reshape(w * m) for o in outs[nk:2 * nk]]
    hashes = host_array(outs[-2]).reshape(w * m)
    live = host_array(outs[-1]).reshape(w, m)
    with timing.span("host.skew_weigh"):
        vc = np.asarray(table.valid_counts, np.float64)
        keep = live.reshape(-1)
        if not keep.any():
            return None
        # each shard contributes its true row share split evenly over its
        # samples (the sample_keys weighting) — scaled to absolute rows
        per_shard_w = np.repeat(
            np.where(vc > 0, vc / np.maximum(m, 1), 0.0), m)
        return ([v[keep] for v in vals], [v[keep] for v in vls],
                hashes[keep], per_shard_w[keep], total)


def _trace_key_sample(mesh):
    w = int(mesh.devices.size)
    cap, S = 1024, jax.ShapeDtypeStruct
    fn = _key_sample_unwrap(_key_sample_fn(mesh, 64, 1))
    fnv = _key_sample_unwrap(_key_sample_fn(mesh, 64, 1, True))

    def both(vc, d, v):
        return fn(vc, d, v), fnv(vc, d, v)

    return jax.make_jaxpr(both)(S((w,), np.int32), S((w * cap,), np.int64),
                                S((w * cap,), np.bool_))


from ..analysis.registry import declare_builder as _declare_builder, \
    unwrap as _key_sample_unwrap  # noqa: E402

_declare_builder(f"{__name__}._key_sample_fn", _trace_key_sample,
                 tags=("profile",))
