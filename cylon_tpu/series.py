"""Series: a named device-resident column with elementwise compute.

TPU-native equivalent of PyCylon's ``Series`` (python/pycylon/pycylon/
series.py) and the dual arrow/numpy "compute engine" behind DataFrame math
and filters (python/pycylon/pycylon/data/compute.pyx:212-218).  The reference
dispatches per-op to pyarrow.compute or numpy on host memory; here every op
is a ``jax.numpy`` expression over the (possibly mesh-sharded) column array —
XLA fuses chains of elementwise ops into single kernels, and padding rows
simply compute garbage that the valid-prefix convention ignores.

Null semantics: validity propagates through arithmetic/comparison as AND
(null op x -> null), matching Arrow/pandas nullable behavior.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import config
from .obs import metrics as _metrics
from .utils.cache import jit, program_cache
from .core.column import Column, DecimalScale
from .core.dtypes import LogicalType, from_numpy_dtype, physical_np_dtype
from .core.table import Table
from .status import CylonTypeError, InvalidError

shard_map = jax.shard_map


def _binop_validity(a: Column, b) -> Any:
    va = a.validity
    vb = b.validity if isinstance(b, Column) else None
    if va is None:
        return vb
    if vb is None:
        return va
    return va & vb


class Series:
    """A column bound to a table's row layout (env + per-shard valid counts).

    Arithmetic/comparison with scalars or layout-matched Series; boolean
    Series feed ``DataFrame.__getitem__`` filters.
    """

    __slots__ = ("name", "_col", "_env", "_valid")

    def __init__(self, name: str, col: Column, env, valid_counts: np.ndarray):
        self.name = name
        self._col = col
        self._env = env
        self._valid = valid_counts

    # -- basics ------------------------------------------------------------
    @property
    def column(self) -> Column:
        return self._col

    @property
    def dtype(self) -> LogicalType:
        return self._col.type

    @property
    def env(self):
        return self._env

    @property
    def valid_counts(self) -> np.ndarray:
        return self._valid

    def __len__(self) -> int:
        return int(self._valid.sum())

    # reference series.py properties: id/data/shape
    @property
    def id(self) -> str:
        return self.name

    @property
    def data(self) -> np.ndarray:
        """Materialized values (valid prefixes compacted across shards,
        string codes decoded) — NOT the raw padded device buffer, which
        holds per-shard padding garbage (use .column.data for that)."""
        return self.to_numpy()

    @property
    def shape(self) -> tuple:
        return (len(self),)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Series({self.name!r}, {self.dtype.value}, len={len(self)})"

    def to_numpy(self) -> np.ndarray:
        w = self._valid.shape[0]
        cap = len(self._col) // max(w, 1)
        host = np.asarray(self._col.data)
        valid = (np.asarray(self._col.validity)
                 if self._col.validity is not None else None)
        parts = [slice(i * cap, i * cap + int(self._valid[i]))
                 for i in range(w)]
        data = np.concatenate([host[s] for s in parts]) if parts else host[:0]
        vcat = (np.concatenate([valid[s] for s in parts])
                if valid is not None else None)
        return Column(data, self._col.type, vcat,
                      self._col.dictionary).to_numpy(len(data))

    def to_pandas(self):
        import pandas as pd
        return pd.Series(self.to_numpy(), name=self.name)

    # -- elementwise machinery --------------------------------------------
    def _wrap(self, data, validity, lt: LogicalType | None = None,
              dictionary=None, name: str | None = None,
              bounds=None) -> "Series":
        lt = lt or from_numpy_dtype(np.dtype(data.dtype))
        return Series(name or self.name,
                      Column(data, lt, validity, dictionary, bounds=bounds),
                      self._env, self._valid)

    def _expr(self, op: str, kind: str, a, b=None, fa: int = 1, fb: int = 1):
        """One elementwise program (:func:`_expr_fn`) over device array
        ``a`` and, for a binary op, array or scalar ``b``; ``kind`` is the
        ``series_expr_dispatches`` label the host decided the op under."""
        _EXPR_DISPATCHES[kind].inc()
        args = (a,) if b is None else (a, b)
        return _expr_fn(self._env.mesh, op,
                        tuple(_operand_kind(x) for x in args), fa, fb)(*args)

    def _other_operand(self, other):
        """-> (device array or scalar, validity or None)."""
        if isinstance(other, Series):
            if other._col.data.shape != self._col.data.shape:
                raise InvalidError("series layouts differ; align first")
            if (other._col.type == LogicalType.STRING) != (
                    self._col.type == LogicalType.STRING):
                raise CylonTypeError("cannot mix string and numeric series")
            if other._col.type == LogicalType.STRING:
                from .relational.common import unify_dictionaries
                a, b = unify_dictionaries(self._col, other._col)
                return (a, b.data), _binop_validity(a, b)
            return (self._col, other._col.data), _binop_validity(
                self._col, other._col)
        # scalar
        if isinstance(other, str):
            raise CylonTypeError("string scalar only valid in comparisons")
        return (self._col, other), self._col.validity

    def _arith(self, other, op: str, name: str) -> "Series":
        if self._col.type == LogicalType.STRING:
            raise CylonTypeError(f"{name} not supported for string series")
        if self._col.type == LogicalType.LIST:
            raise CylonTypeError(f"{name} not supported for list series")
        if self._col.type == LogicalType.DECIMAL or (
                isinstance(other, Series)
                and other._col.type == LogicalType.DECIMAL):
            return self._decimal_arith(other, op, name)
        (col, rhs), validity = self._other_operand(other)
        unary = op in ("neg", "abs")
        floating = op in ("truediv", "rtruediv") or any(
            np.dtype(getattr(x, "dtype", type(x))).kind == "f"
            for x in (col.data, rhs))
        out = self._expr(op, "float" if floating else "int", col.data,
                         None if unary else rhs)
        bounds = None
        if out.dtype == jnp.int64:
            rb = other._col.bounds if isinstance(other, Series) else (
                (other, other) if type(other) is int else None)
            bounds = _interval(op, col.bounds, rb)
            if bounds is not None and not (_I64[0] <= bounds[0]
                                           and bounds[1] <= _I64[1]):
                bounds = None       # int64 wraps, as numpy's does
        return self._wrap(out, validity, bounds=bounds)

    def _decimal_arith(self, other, op: str, name: str) -> "Series":
        """Scale-exact DECIMAL arithmetic on the scaled integers - THE rule
        (docs/decimal.md):

        * ``*``: the scaled integers multiply; scale ``s1 + s2``, precision
          ``p1 + p2``.
        * ``+`` / ``-``: both sides are brought to the larger scale ``s``
          (an exact ``10^d`` multiply) and added; precision
          ``max(p1 + s - s1, p2 + s - s2) + 1``.  Negation and ``abs`` keep
          scale and precision.
        * An integer column is DECIMAL of scale 0 whose precision is its
          bounds' digits (19 where it has none); an ``int`` or
          ``decimal.Decimal`` literal is DECIMAL of its own digits and
          scale.  So ``decimal * integer`` keeps the scale and ``1 - d``
          is at ``d``'s scale.  A float operand raises (lossy); ``/``,
          ``//``, ``%`` and ``**`` are not defined here.
        * Nothing overflows silently.  The result's ``Column.bounds`` are
          the interval arithmetic of the operands' bounds (:func:`_interval`
          - the one function INT64 ``+ - *`` use too); where there are
          bounds the precision is no more than their digits.  A result
          precision past 18 - int64's - raises: no bounds and a rule
          precision past 18, or bounds that themselves pass 18 digits."""
        if op not in ("add", "sub", "rsub", "mul", "neg", "abs"):
            raise CylonTypeError(
                f"{name} on decimal series is not supported (only + - * "
                "are scale-exact); cast to float64 first "
                "(astype('float64') of the unscaled integers, then "
                "divide by 10**scale)")
        unary = op in ("neg", "abs")
        if isinstance(other, Series) and not unary \
                and other._col.data.shape != self._col.data.shape:
            raise InvalidError("series layouts differ; align first")
        a, sa, pa, ba = _decimal_operand(self, name)
        b, sb, pb, bb = (None, sa, pa, ba) if unary \
            else _decimal_operand(other, name)
        fa = fb = 1
        if op == "mul":
            scale, prec = sa + sb, pa + pb
        elif unary:
            scale, prec = sa, pa
        else:
            scale = max(sa, sb)
            fa, fb = 10 ** (scale - sa), 10 ** (scale - sb)
            prec = max(pa + scale - sa, pb + scale - sb) + 1
            ba, bb = _interval("mul", ba, (fa, fa)), \
                _interval("mul", bb, (fb, fb))
            if not isinstance(other, Series):
                b, fb = b * fb, 1           # a literal is scaled on the host
        bounds = _interval(op, ba, bb)
        if bounds is not None:
            prec = min(prec, _digits(max(abs(bounds[0]), abs(bounds[1]))))
        if prec > 18:
            raise CylonTypeError(
                f"{name} on decimal series: the result needs precision "
                f"{prec} > 18, which int64 does not hold, and "
                + ("the operands' bounds do not rule the overflow out"
                   if bounds is not None else
                   "an operand carries no value bounds that would prove "
                   "the values smaller (typed ingest sets them; a filter, "
                   "join or sort keeps them)")
                + "; cast to float64 first (astype('float64')) and "
                "accept its rounding")
        validity = self._col.validity if not isinstance(other, Series) \
            else _binop_validity(self._col, other._col)
        out = self._expr(op, "decimal", a, b, fa, fb)
        return self._wrap(out, validity, LogicalType.DECIMAL,
                          DecimalScale(prec, scale), bounds=bounds)

    def _compare(self, other, op: str) -> "Series":
        if self._col.type == LogicalType.LIST or (
                isinstance(other, Series)
                and other._col.type == LogicalType.LIST):
            raise CylonTypeError(
                "comparisons on list passthrough series are not supported")
        if self._col.type == LogicalType.DECIMAL:
            import decimal
            sc = self._col.dictionary
            if isinstance(other, Series) \
                    and other._col.type == LogicalType.DECIMAL:
                from .relational.common import rescale_decimal_pair
                a, b = rescale_decimal_pair(self._col, other._col)
                return self._wrap(self._expr(op, "compare", a.data, b.data),
                                  _binop_validity(a, b), LogicalType.BOOL)
            if isinstance(other, (int, decimal.Decimal)):
                q = decimal.Decimal(other).scaleb(sc.scale)
                if q != int(q):
                    raise CylonTypeError(
                        f"literal {other!r} has more fractional digits "
                        f"than the column scale {sc.scale}")
                return self._wrap(
                    self._expr(op, "compare", self._col.data, int(q)),
                    self._col.validity, LogicalType.BOOL)
            raise CylonTypeError(
                "decimal compares need a Decimal/int literal or another "
                "decimal series (float literals are lossy)")
        if isinstance(other, str):
            if self._col.type != LogicalType.STRING:
                raise CylonTypeError("string scalar vs numeric series")
            from .core.column import HashedStrings
            if isinstance(self._col.dictionary, HashedStrings):
                # hashed codes have no lexical order: equality only
                if op not in ("eq", "ne"):
                    raise CylonTypeError(
                        "ordered compare on a high-cardinality hashed "
                        "string column is not supported (== and != work)")
                h = int(self._col.dictionary.hash_values([other])[0])
                out = self._expr(op, "compare", self._col.data, np.int64(h))
                return self._wrap(out, self._col.validity, LogicalType.BOOL)
            # dictionary is sorted, so codes are order-isomorphic to values;
            # an absent scalar compares as its insertion point - 1/2, in
            # doubled integers (2 * code against 2 * pos - 1: exact, and
            # no float64 on the device)
            d = self._col.dictionary
            pos = int(np.searchsorted(d, other))
            present = pos < len(d) and d[pos] == other
            out = self._expr(op, "compare", self._col.data,
                             np.int32(pos if present else 2 * pos - 1),
                             fa=1 if present else 2)
            return self._wrap(out, self._col.validity, LogicalType.BOOL)
        (col, rhs), validity = self._other_operand(other)
        if op not in ("eq", "ne"):
            # series-vs-series ordered compare: hashed string codes carry
            # no lexical order (codes would compare by hash — silently
            # wrong, never allowed)
            from .core.column import HashedStrings
            for c in (col, getattr(other, "_col", None)):
                if c is not None and isinstance(
                        getattr(c, "dictionary", None), HashedStrings):
                    raise CylonTypeError(
                        "ordered compare on a high-cardinality hashed "
                        "string column is not supported (== and != work)")
        out = self._expr(op, "compare", col.data, rhs)
        return self._wrap(out, validity, LogicalType.BOOL)

    # arithmetic
    def __add__(self, o):
        return self._arith(o, "add", "+")

    def __radd__(self, o):
        return self._arith(o, "add", "+")

    def __sub__(self, o):
        return self._arith(o, "sub", "-")

    def __rsub__(self, o):
        return self._arith(o, "rsub", "-")

    def __mul__(self, o):
        return self._arith(o, "mul", "*")

    def __rmul__(self, o):
        return self._arith(o, "mul", "*")

    def __truediv__(self, o):
        return self._arith(o, "truediv", "/")

    def __rtruediv__(self, o):
        return self._arith(o, "rtruediv", "/")

    def __floordiv__(self, o):
        return self._arith(o, "floordiv", "//")

    def __mod__(self, o):
        return self._arith(o, "mod", "%")

    def __pow__(self, o):
        return self._arith(o, "pow", "**")

    def __neg__(self):
        return self._arith(0, "neg", "neg")

    def __abs__(self):
        return self._arith(0, "abs", "abs")

    # comparisons
    def __eq__(self, o):  # type: ignore[override]
        return self._compare(o, "eq")

    def __ne__(self, o):  # type: ignore[override]
        return self._compare(o, "ne")

    def __lt__(self, o):
        return self._compare(o, "lt")

    def __le__(self, o):
        return self._compare(o, "le")

    def __gt__(self, o):
        return self._compare(o, "gt")

    def __ge__(self, o):
        return self._compare(o, "ge")

    __hash__ = None  # type: ignore[assignment]

    # logical
    def _logical(self, other, op: str) -> "Series":
        if self._col.type != LogicalType.BOOL:
            raise CylonTypeError("logical op on non-bool series")
        (col, rhs), validity = self._other_operand(other)
        return self._wrap(self._expr(op, "mask", col.data, rhs), validity,
                          LogicalType.BOOL)

    def __and__(self, o):
        return self._logical(o, "and")

    def __or__(self, o):
        return self._logical(o, "or")

    def __xor__(self, o):
        return self._logical(o, "xor")

    def __invert__(self):
        if self._col.type != LogicalType.BOOL:
            raise CylonTypeError("~ on non-bool series")
        return self._wrap(self._expr("not", "mask", self._col.data),
                          self._col.validity, LogicalType.BOOL)

    # -- null handling -----------------------------------------------------
    def isna(self) -> "Series":
        if self._col.validity is None:
            if self._col.type in (LogicalType.FLOAT32, LogicalType.FLOAT64):
                return self._wrap(jnp.isnan(self._col.data), None,
                                  LogicalType.BOOL)
            # zeros_like preserves the source's device/sharding (never the
            # default backend, unlike a bare jnp.zeros)
            return self._wrap(jnp.zeros_like(self._col.data, dtype=bool),
                              None, LogicalType.BOOL)
        out = jnp.logical_not(self._col.validity)
        if self._col.type in (LogicalType.FLOAT32, LogicalType.FLOAT64):
            out = out | jnp.isnan(self._col.data)
        return self._wrap(out, None, LogicalType.BOOL)

    def notna(self) -> "Series":
        return ~self.isna()

    def where(self, cond: "Series", other=None) -> "Series":
        """Rows where ``cond`` holds keep their value; the rest become
        ``other`` (default: null) — pandas ``Series.where`` (null conds
        never select, like every filter-on-bool site)."""
        if not isinstance(cond, Series):
            raise CylonTypeError("where condition must be a Series")
        if cond._col.type != LogicalType.BOOL:
            raise CylonTypeError("where condition must be boolean")
        from .relational.common import valid_flag
        keep = valid_flag(cond._col)
        if other is None:
            v = keep if self._col.validity is None \
                else (self._col.validity & keep)
            return self._wrap(self._col.data, v)
        return self._fill_where(jnp.logical_not(keep), value=other)

    def fillna(self, value) -> "Series":
        # mask covers every invalid slot -> the result is fully valid
        return self._fill_where(self.isna()._col.data, value,
                                all_valid=True)

    def _fill_where(self, mask, value, all_valid: bool = False) -> "Series":
        """Replace positions where ``mask`` (bool data array) holds with
        ``value``; the filled positions become valid.  Backs ``fillna``
        (mask = isna, all_valid=True since every null gets filled) and
        ``DataFrame.where`` (mask = ~cond)."""
        if self._col.type == LogicalType.STRING:
            if not isinstance(value, str):
                raise CylonTypeError("fill on string series needs str")
            from .core.column import HashedStrings
            d = self._col.dictionary
            if isinstance(d, HashedStrings):
                code = int(d.hash_values([value])[0])
                newd = d.merged_with(HashedStrings(
                    np.asarray([code]).astype(np.int64).view(np.uint64),
                    np.asarray([value], dtype=object)))
                data = jnp.where(mask, jnp.int64(code), self._col.data)
                v2 = None if (all_valid or self._col.validity is None) \
                    else (self._col.validity | mask)
                return self._wrap(data, v2, LogicalType.STRING, newd)
            pos = int(np.searchsorted(d, value))
            if not (pos < len(d) and d[pos] == value):
                newd = np.insert(d, pos, value)
                remap = np.searchsorted(newd, d).astype(np.int32)
                codes = jnp.take(remap,
                                 jnp.clip(self._col.data, 0, len(d) - 1))
                col = Column(codes, LogicalType.STRING, self._col.validity,
                             newd)
            else:
                col = self._col
            code = int(np.searchsorted(col.dictionary, value))
            data = jnp.where(mask, jnp.int32(code), col.data)
            v = None if (all_valid or col.validity is None) \
                else (col.validity | mask)
            return self._wrap(data, v, LogicalType.STRING, col.dictionary)
        data = jnp.where(mask, np.asarray(value, self._col.data.dtype),
                         self._col.data)
        v = None if (all_valid or self._col.validity is None) \
            else (self._col.validity | mask)
        return self._wrap(data, v, self._col.type)

    def astype(self, dtype) -> "Series":
        lt = from_numpy_dtype(np.dtype(dtype)) if not isinstance(
            dtype, LogicalType) else dtype
        return Series(self.name, self._col.cast(lt), self._env, self._valid)

    # -- reductions --------------------------------------------------------
    def _reduce(self, kind: str):
        from .relational.common import live_mask, REP, ROW
        col, valid, lt = self._col, self._valid, self._col.type
        if lt == LogicalType.STRING and kind not in ("count", "min", "max"):
            raise CylonTypeError(f"{kind} on string series")
        from .core.column import HashedStrings
        if (lt == LogicalType.STRING and kind in ("min", "max")
                and isinstance(col.dictionary, HashedStrings)):
            raise CylonTypeError(
                f"{kind} on a high-cardinality hashed string series: "
                "hashed codes carry no lexical order")
        mesh = self._env.mesh
        cap = len(col) // max(valid.shape[0], 1)
        out, cnt = _reduce_fn(mesh, kind, max(cap, 1))(
            np.asarray(valid, np.int32), col.data,
            col.validity if col.validity is not None
            else np.ones(len(col), bool))
        # partials keep the accumulator dtype (int64 stays int64 — no float64
        # round-trip that would lose precision past 2^53)
        parts = np.asarray(out)
        cnts = np.asarray(cnt)
        if kind == "sum":
            if lt not in (LogicalType.FLOAT32, LogicalType.FLOAT64):
                return int(parts.sum())
            return float(parts.sum())
        if kind == "count":
            return int(parts.sum())
        live = cnts > 0
        if not live.any():
            # pandas: min/max of empty / all-NaN numeric series is nan
            return None if lt == LogicalType.STRING else float("nan")
        v = parts[live].min() if kind == "min" else parts[live].max()
        if lt == LogicalType.STRING:
            from .core.column import HashedStrings
            if isinstance(self._col.dictionary, HashedStrings):
                return str(self._col.dictionary.take(
                    np.asarray([int(v)], np.int64))[0])
            return str(self._col.dictionary[int(v)])
        if lt in (LogicalType.FLOAT32, LogicalType.FLOAT64):
            return float(v)
        return int(v)

    def sum(self):
        return self._reduce("sum")

    def count(self) -> int:
        return self._reduce("count")

    def min(self):
        return self._reduce("min")

    def max(self):
        return self._reduce("max")

    def mean(self):
        c = self.count()
        return self.sum() / c if c else float("nan")

    def nunique(self) -> int:
        import pandas as pd
        from .relational import unique_table
        t = Table({self.name: self._col}, self._env, self._valid)
        vals = unique_table(t, [self.name]).to_pandas()[self.name]
        return int(pd.notna(vals).sum())  # pandas semantics: drop nulls

    def unique(self) -> np.ndarray:
        from .relational import unique_table
        t = Table({self.name: self._col}, self._env, self._valid)
        return unique_table(t, [self.name]).to_pandas()[self.name].to_numpy()


@program_cache()
def _reduce_fn(mesh: Mesh, kind: str, cap: int):
    from .relational.common import REP, ROW, live_mask

    def per_shard(vc, data, validity):
        mask = live_mask(vc, cap) & validity
        if data.dtype.kind == "f":
            mask = mask & ~jnp.isnan(data)  # pandas skipna=True
        if kind == "sum":
            out = jnp.sum(jnp.where(mask, data, 0))
            cnt = jnp.sum(mask)
        elif kind == "count":
            out = jnp.sum(mask)
            cnt = out
        elif kind == "min":
            big = jnp.iinfo(data.dtype).max if data.dtype.kind in "iu" \
                else jnp.inf
            out = jnp.min(jnp.where(mask, data, big))
            cnt = jnp.sum(mask)
        elif kind == "max":
            small = jnp.iinfo(data.dtype).min if data.dtype.kind in "iu" \
                else -jnp.inf
            out = jnp.max(jnp.where(mask, data, small))
            cnt = jnp.sum(mask)
        else:
            raise ValueError(kind)
        # dtype-preserving partials: int64 sums stay exact past 2^53
        return out.reshape(1), cnt.astype(jnp.int64).reshape(1)

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(REP, ROW, ROW),
                             out_specs=(ROW, ROW)))


# ---------------------------------------------------------------------------
# the elementwise expression layer: one builder, one stage, one counter
# ---------------------------------------------------------------------------

_I64 = (-(1 << 63), (1 << 63) - 1)

#: one count an elementwise op, by the kind the host decided it under
_EXPR_DISPATCHES = {
    kind: _metrics.counter("series_expr_dispatches", kind=kind)
    for kind in ("decimal", "int", "float", "compare", "mask")}

_EXPR_OPS = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "rsub": lambda a, b: jnp.subtract(b, a),
    "truediv": jnp.true_divide,
    "rtruediv": lambda a, b: jnp.true_divide(b, a),
    "floordiv": jnp.floor_divide, "mod": jnp.mod, "pow": jnp.power,
    "neg": jnp.negative, "abs": jnp.abs,
    "eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less,
    "le": jnp.less_equal, "gt": jnp.greater, "ge": jnp.greater_equal,
    "and": jnp.logical_and, "or": jnp.logical_or, "xor": jnp.logical_xor,
    "not": jnp.logical_not,
}


#: integer column types a DECIMAL expression takes as scale-0 decimals
#: (not UINT64: its values pass int64; not dates, which are no numbers)
_DECIMAL_INT_PARTNERS = frozenset({
    LogicalType.INT8, LogicalType.INT16, LogicalType.INT32,
    LogicalType.INT64, LogicalType.UINT8, LogicalType.UINT16,
    LogicalType.UINT32})


def _operand_kind(x) -> str:
    """What of an operand the program depends on: an array's dtype, a
    scalar's type (a Python scalar stays weakly typed through ``jit``, so
    ``int_column * 2`` promotes as it did eagerly)."""
    dt = getattr(x, "dtype", None)
    return type(x).__name__ if dt is None else (
        str(dt) if getattr(x, "ndim", 0) else f"scalar:{dt}")


def _digits(n: int) -> int:
    return max(len(str(abs(int(n)))), 1)


def _interval(op: str, a, b):
    """Bounds ``(lo, hi)`` of ``a <op> b`` from the operands' bounds, in
    Python integers (no width): None where either is unknown.  The one
    bounds rule of the expression layer - DECIMAL and INT64 ``+ - *``,
    negation and ``abs``."""
    if a is None or (b is None and op not in ("neg", "abs")):
        return None
    if op == "add":
        return a[0] + b[0], a[1] + b[1]
    if op == "sub":
        return a[0] - b[1], a[1] - b[0]
    if op == "rsub":
        return b[0] - a[1], b[1] - a[0]
    if op == "mul":
        c = [x * y for x in a for y in b]
        return min(c), max(c)
    if op == "neg":
        return -a[1], -a[0]
    if op == "abs":
        lo = 0 if a[0] <= 0 <= a[1] else min(abs(a[0]), abs(a[1]))
        return lo, max(abs(a[0]), abs(a[1]))
    return None


def _decimal_operand(x, name: str):
    """``(device array or Python int, scale, precision, bounds)`` of one
    side of a DECIMAL expression (:meth:`Series._decimal_arith`)."""
    import decimal
    if isinstance(x, Series):
        c = x._col
        if c.type == LogicalType.DECIMAL:
            return c.data, c.dictionary.scale, c.dictionary.precision, \
                c.bounds
        if c.type in _DECIMAL_INT_PARTNERS:
            prec = 19 if c.bounds is None else _digits(
                max(abs(c.bounds[0]), abs(c.bounds[1])))
            return c.data.astype(jnp.int64), 0, prec, c.bounds
        raise CylonTypeError(
            f"{name} of a decimal and a {c.type.value} series is not "
            "scale-exact (float operands are lossy); cast one side")
    if isinstance(x, bool) or not isinstance(x, (int, decimal.Decimal)):
        raise CylonTypeError(
            f"{name} on decimal series needs a Decimal/int literal, an "
            "integer series or another decimal series (float literals "
            "are lossy)")
    d = decimal.Decimal(x)
    if not d.is_finite():
        raise CylonTypeError(f"non-finite decimal literal {x!r}")
    scale = max(-d.as_tuple().exponent, 0)
    v = int(d.scaleb(scale))
    return v, scale, _digits(v), (v, v)


@program_cache()
def _expr_fn(mesh: Mesh, op: str, kinds: tuple, fa: int = 1, fb: int = 1):
    """THE elementwise program of the ``DataFrame`` layer:
    ``op(a * fa[, b * fb])`` over row-sharded arrays (``b`` may be a
    scalar), under stage ``expr``.  ``fa`` / ``fb`` are the exact ``10^d``
    rescales of a DECIMAL pair (1: no multiply).  ``kinds`` - the
    operands' dtypes - only keys the cache: one program a signature.
    Plain ``jit``: an elementwise op keeps its operands' sharding."""
    from .utils.stages import staged
    fn = _EXPR_OPS[op]

    def scaled(x, f):
        return x if f == 1 else x * f

    if len(kinds) == 1:
        def expr(a):
            return fn(scaled(a, fa))
    else:
        def expr(a, b):
            return fn(scaled(a, fa), scaled(b, fb))

    return jit(staged("expr")(expr))


def _trace_expr(op: str, scalar: bool):
    def trace(mesh):
        from .analysis.registry import decl_shapes, unwrap
        w, cap, S = decl_shapes(mesh)
        a = S((w * cap,), np.int64)
        b = 3 if scalar else a
        return jax.make_jaxpr(unwrap(_expr_fn(
            mesh, op, (_operand_kind(a), _operand_kind(b)), 10, 1)))(a, b)
    return trace


from .analysis.registry import declare_builder  # noqa: E402

declare_builder(f"{__name__}._expr_fn[mul]", _trace_expr("mul", False),
                tags=("expr",))
declare_builder(f"{__name__}._expr_fn[lt_scalar]", _trace_expr("lt", True),
                tags=("expr",))
