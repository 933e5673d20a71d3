"""DataFrame: the pandas-like user API.

TPU-native equivalent of PyCylon's ``DataFrame`` veneer (reference
python/pycylon/pycylon/frame.py:187, GroupByDataFrame :122) preserving the
reference's dispatch contract (frame.py:2063-2076): every operator takes
``env: CylonEnv = None`` — ``None`` runs the op locally (serial world), an
env runs it distributed over that env's device mesh.  A DataFrame built
without an env lives on the default local device; passing ``env=`` to an op
(or the constructor) moves/keeps it on the mesh.

Column math and filters go through :class:`cylon_tpu.series.Series`
(reference compute.pyx engine).
"""

from __future__ import annotations

from typing import Any, Iterable, Mapping, Sequence

import numpy as np

from .core.column import Column
from .core.table import Table, default_env
from .ctx.context import CylonEnv
from .relational import (concat_tables, equals, filter_table,
                         groupby_aggregate, head, join_tables, repartition,
                         set_operation, shuffle_table, slice_table,
                         sort_table, tail, unique_table)
from .series import Series
from .status import CylonKeyError, InvalidError

__all__ = ["DataFrame", "GroupByDataFrame", "concat", "read_pandas"]


def _check_join_algorithm(algorithm: str) -> None:
    """The reference's SORT|HASH join choice (join_config.hpp:25,37).  On
    TPU the single-sort merge dominates a hash build/probe at every
    build-side size (measured v5e: ≥15.5 ns/row per random probe gather vs
    ~3.5 ns/row sort operand + ~1.7/payload lane; see docs/DESIGN.md
    "HASH join option"), so "hash" warns and runs the sort path."""
    if algorithm == "sort":
        return
    if algorithm == "hash":
        import warnings
        warnings.warn(
            "algorithm='hash' is not implemented on TPU: a hash probe "
            "costs >=15.5 ns/row (random gather) vs ~3.5 ns/row for a "
            "sort operand, so the single-sort merge join is used instead "
            "(see docs/DESIGN.md)", UserWarning, stacklevel=3)
        return
    raise InvalidError(f"algorithm must be 'sort' or 'hash', got "
                       f"{algorithm!r}")


def _resolve_env(df_env: CylonEnv, env: CylonEnv | None) -> CylonEnv:
    return env if env is not None else df_env


class DataFrame:
    """Columnar distributed dataframe over a device mesh."""

    def __init__(self, data: Any = None, env: CylonEnv | None = None,
                 _table: Table | None = None):
        self._index: str | None = None  # label index column (C24 analog)
        self._index_drop: bool = True   # pandas set_index drop semantics
        if _table is not None:
            self._table = _table
            return
        if data is None:
            data = {}
        if isinstance(data, Table):
            self._table = data
        elif isinstance(data, DataFrame):
            self._table = data._table
        elif isinstance(data, Mapping):
            self._table = Table.from_pydict(dict(data), env)
        elif isinstance(data, (list, tuple)):
            # list of columns (PyCylon accepts list-of-lists)
            cols = {f"{i}": np.asarray(c) for i, c in enumerate(data)}
            self._table = Table.from_pydict(cols, env)
        else:
            try:
                import pandas as pd
                if isinstance(data, pd.DataFrame):
                    self._table = Table.from_pandas(data, env)
                else:
                    raise TypeError
            except TypeError:
                raise InvalidError(f"cannot build DataFrame from {type(data)}")

    # -- construction helpers ---------------------------------------------
    @staticmethod
    def from_table(table: Table) -> "DataFrame":
        return DataFrame(_table=table)

    @property
    def table(self) -> Table:
        return self._table

    @property
    def env(self) -> CylonEnv:
        return self._table.env

    def _to_env(self, env: CylonEnv) -> "DataFrame":
        """Move this frame onto another env's mesh (host round-trip)."""
        if env is self._table.env:
            return self
        return DataFrame(self.to_pandas(), env=env)

    def _index_cols(self) -> list:
        """Index column names as a list: [] (range index), one name, or
        several (multi-index, reference index.hpp:36 over indexer.hpp:76)."""
        if self._index is None:
            return []
        if isinstance(self._index, tuple):
            return list(self._index)
        return [self._index]

    def _wrap(self, table: Table, keep_index: bool = False) -> "DataFrame":
        out = DataFrame(_table=table)
        idx = self._index_cols()
        if keep_index and idx and all(c in table.column_names for c in idx):
            out._index = self._index
            out._index_drop = self._index_drop
        return out

    def _hidden(self) -> set:
        """Columns present in the physical table but not user-visible (a
        dropped-into-index column)."""
        if self._index is not None and self._index_drop:
            return set(self._index_cols())
        return set()

    def _visible_table(self) -> Table:
        hid = self._hidden()
        return self._table.drop(hid) if hid else self._table

    # -- schema / introspection -------------------------------------------
    @property
    def columns(self) -> list[str]:
        hid = self._hidden()
        return [c for c in self._table.column_names if c not in hid]

    @property
    def shape(self) -> tuple[int, int]:
        return (self._table.row_count, len(self.columns))

    @property
    def dtypes(self) -> dict[str, str]:
        hid = self._hidden()
        return {f.name: f.type.value for f in self._table.schema
                if f.name not in hid}

    def __len__(self) -> int:
        return self._table.row_count

    def __contains__(self, name: str) -> bool:
        return name in self._table and name not in self._hidden()

    def __repr__(self) -> str:  # pragma: no cover
        n = len(self)
        show = self.to_pandas() if n <= 20 else head(self._table, 10).to_pandas()
        s = repr(show)
        if n > 20:
            s += f"\n... ({n} rows x {self._table.column_count} cols, " \
                 f"world={self.env.world_size})"
        return s

    # -- index (reference indexing subsystem, indexing/index.hpp) ----------
    @property
    def loc(self):
        from .indexing.indexer import LocIndexer
        return LocIndexer(self)

    @property
    def iloc(self):
        from .indexing.indexer import ILocIndexer
        return ILocIndexer(self)

    @property
    def index(self):
        if self._index is None:
            return np.arange(len(self))
        idx = self._index_cols()
        if len(idx) == 1:
            return self._col_series(idx[0]).to_numpy()
        import pandas as pd
        return pd.MultiIndex.from_arrays(
            [self._col_series(c).to_numpy() for c in idx], names=idx)

    def set_index(self, name, drop: bool = True) -> "DataFrame":
        """Use column ``name`` (or a LIST of columns — multi-index,
        reference index.hpp:36 / indexer.hpp:76) as the row-label index
        (reference Table::SetArrowIndex, table.hpp:164).  ``drop`` follows
        pandas: drop=True (default) removes the column(s) from the visible
        columns — they live on as the index (physically retained for loc)
        — while drop=False keeps them addressable as data columns too."""
        names = [name] if isinstance(name, str) else list(name)
        if not names:
            raise CylonKeyError("set_index needs at least one column")
        for n in names:
            if n not in self._table:
                raise CylonKeyError(f"no column {n!r}")
        out = DataFrame(_table=self._table)
        out._index = names[0] if len(names) == 1 else tuple(names)
        out._index_drop = bool(drop)
        return out

    def reset_index(self) -> "DataFrame":
        """Demote the index back to a regular column (pandas semantics —
        the physical column was retained, so this is metadata-only)."""
        out = DataFrame(_table=self._table)
        return out

    # -- materialization ---------------------------------------------------
    def to_pandas(self):
        df = self._table.to_pandas()
        idx = self._index_cols()
        if idx:
            df = df.set_index(idx if len(idx) > 1 else idx[0],
                              drop=self._index_drop)
            if not self._index_drop and len(idx) == 1:
                # pandas keeps the column AND names the index after it
                df.index.name = idx[0]
        return df

    def to_arrow(self):
        return self._table.to_arrow()

    def to_numpy(self) -> np.ndarray:
        return self.to_pandas().to_numpy()

    def to_dict(self) -> dict:
        return {k: v.tolist()
                for k, v in self.to_pandas().to_dict("list").items()}

    # -- column access / mutation -----------------------------------------
    def _col_series(self, name: str) -> "Series":
        """Internal column access that ignores index-hiding (used by the
        loc/iloc machinery, which must read the index column)."""
        return Series(name, self._table.column(name), self.env,
                      self._table.valid_counts)

    def __getitem__(self, key):
        if isinstance(key, str):
            if key in self._hidden():
                raise CylonKeyError(
                    f"{key!r} is the index (set_index drop=True)")
            col = self._table.column(key)
            return Series(key, col, self.env, self._table.valid_counts)
        if isinstance(key, (list, tuple)) and all(isinstance(k, str)
                                                  for k in key):
            return self._wrap(self._table.project(key))
        if isinstance(key, Series):
            if key.dtype.value != "bool":
                raise InvalidError("filter mask must be a bool series")
            from .relational.common import valid_flag
            return self._wrap(filter_table(self._table,
                                           valid_flag(key.column)),
                              keep_index=True)
        if isinstance(key, slice):
            start, stop, step = key.indices(len(self))
            if step != 1:
                raise InvalidError("slice step not supported")
            return self._wrap(slice_table(self._table, start, stop - start),
                              keep_index=True)
        raise CylonKeyError(f"cannot index DataFrame with {key!r}")

    def __setitem__(self, name: str, value):
        if not isinstance(name, str):
            raise CylonKeyError("column name must be a string")
        if isinstance(value, Series):
            # same capacity is not enough: a column from a differently-
            # partitioned frame would silently misalign rows across shards
            if (value.column.data.shape[0] != self._table.capacity *
                    self.env.world_size
                    or not np.array_equal(value.valid_counts,
                                          self._table.valid_counts)):
                raise InvalidError("series layout mismatch")
            col = value.column
        elif np.isscalar(value) or isinstance(value, (int, float, bool, str)):
            n = len(self)
            col = self._ingest_column(np.full(n, value))
        else:
            arr = np.asarray(value)
            if arr.shape[0] != len(self):
                raise InvalidError(
                    f"column length {arr.shape[0]} != rows {len(self)}")
            col = self._ingest_column(arr)
        self._table = self._table.with_columns({name: col})

    def _ingest_column(self, arr: np.ndarray) -> Column:
        """Host array -> column matching this table's shard layout."""
        tmp = Table.from_pydict({"__c": arr}, self.env)
        tmp = repartition(tmp, tuple(int(x) for x in self._table.valid_counts))
        from .relational.repart import repad_table
        tmp = repad_table(tmp, self._table.capacity)
        return tmp.column("__c")

    def drop(self, columns: Iterable[str]) -> "DataFrame":
        if isinstance(columns, str):
            columns = [columns]
        return self._wrap(self._table.drop(columns))

    def rename(self, columns: Mapping[str, str]) -> "DataFrame":
        return self._wrap(self._table.rename(columns))

    # -- relational operators (the reference's Table API surface) ----------
    def merge(self, right: "DataFrame", how: str = "inner", on=None,
              left_on=None, right_on=None, suffixes=("_x", "_y"),
              env: CylonEnv | None = None, algorithm: str = "sort") -> "DataFrame":
        """pandas.merge parity (reference frame.py:1852 + dispatch :2063).

        ``algorithm``: the reference offers SORT|HASH (join_config.hpp:25);
        on TPU every join runs the single-sort merge — a hash build/probe
        needs ≥1 random gather per probe row (~15.5 ns/row measured on
        v5e) while a sort operand costs ~3.5 ns/row, so the sort path
        dominates at every build-side size (docs/DESIGN.md).  Passing
        ``algorithm="hash"`` warns and uses sort."""
        _check_join_algorithm(algorithm)
        env = _resolve_env(self.env, env)
        lhs, rhs = self._to_env(env), right._to_env(env)
        if on is not None:
            left_on = right_on = on
        if left_on is None or right_on is None:
            common = [c for c in lhs.columns if c in set(rhs.columns)]
            if not common:
                raise InvalidError("no common columns to merge on")
            left_on = right_on = common
        t = join_tables(lhs._visible_table(), rhs._visible_table(),
                        left_on, right_on, how=how,
                        suffixes=suffixes, coalesce_keys=True)
        return self._wrap(t)

    def join(self, other: "DataFrame", how: str = "left", on=None,
             lsuffix: str = "l", rsuffix: str = "r",
             env: CylonEnv | None = None, algorithm: str = "sort") -> "DataFrame":
        """Key-based join with suffixed columns (reference frame.py:1723
        joins add suffixes to every overlapping column, keys kept apart).
        ``algorithm`` as in :meth:`merge`."""
        _check_join_algorithm(algorithm)
        env = _resolve_env(self.env, env)
        lhs, oth = self._to_env(env), other._to_env(env)
        if on is None:
            raise InvalidError("join requires on= key column(s)")
        on = [on] if isinstance(on, str) else list(on)
        t = join_tables(lhs._visible_table(), oth._visible_table(), on, on,
                        how=how, suffixes=(lsuffix, rsuffix),
                        coalesce_keys=False)
        return self._wrap(t)

    def sort_values(self, by, ascending=True, nulls_position: str = "last",
                    env: CylonEnv | None = None,
                    method: str = "initial") -> "DataFrame":
        """``method``: "initial" (sample-first) or "regular" (local-sort
        first, quantile-exact splitters) — the reference's two distributed
        sort strategies (SortOptions, table.cpp:761)."""
        env = _resolve_env(self.env, env)
        return self._wrap(sort_table(self._to_env(env)._table, by,
                                     ascending=ascending,
                                     nulls_position=nulls_position,
                                     method=method),
                          keep_index=True)

    def groupby(self, by, env: CylonEnv | None = None) -> "GroupByDataFrame":
        env = _resolve_env(self.env, env)
        by = [by] if isinstance(by, str) else list(by)
        return GroupByDataFrame(self._to_env(env), by)

    def drop_duplicates(self, subset=None, keep: str = "first",
                        env: CylonEnv | None = None) -> "DataFrame":
        env = _resolve_env(self.env, env)
        d = self._to_env(env)
        if subset is None:
            subset = d.columns  # visible columns only, pandas semantics
        return d._wrap(unique_table(d._table, subset, keep), keep_index=True)

    def union(self, other: "DataFrame", env: CylonEnv | None = None) -> "DataFrame":
        env = _resolve_env(self.env, env)
        return self._wrap(set_operation(self._to_env(env)._table,
                                        other._to_env(env)._table, "union"))

    def intersect(self, other: "DataFrame", env: CylonEnv | None = None) -> "DataFrame":
        env = _resolve_env(self.env, env)
        return self._wrap(set_operation(self._to_env(env)._table,
                                        other._to_env(env)._table, "intersect"))

    def subtract(self, other: "DataFrame", env: CylonEnv | None = None) -> "DataFrame":
        env = _resolve_env(self.env, env)
        return self._wrap(set_operation(self._to_env(env)._table,
                                        other._to_env(env)._table, "subtract"))

    def shuffle(self, on, env: CylonEnv | None = None) -> "DataFrame":
        env = _resolve_env(self.env, env)
        on = [on] if isinstance(on, str) else list(on)
        return self._wrap(shuffle_table(self._to_env(env)._table, on))

    def repartition(self, rows_per_partition=None,
                    env: CylonEnv | None = None) -> "DataFrame":
        env = _resolve_env(self.env, env)
        return self._wrap(repartition(self._to_env(env)._table,
                                      rows_per_partition))

    def head(self, n: int = 5) -> "DataFrame":
        return self._wrap(head(self._table, n), keep_index=True)

    def tail(self, n: int = 5) -> "DataFrame":
        return self._wrap(tail(self._table, n), keep_index=True)

    def to_csv(self, path, **kw) -> None:
        from .io import write_csv
        write_csv(self._table, path, **kw)

    def to_parquet(self, path, **kw) -> None:
        from .io import write_parquet
        write_parquet(self._table, path, **kw)

    def to_json(self, path, **kw) -> None:
        from .io import write_json
        write_json(self._table, path, **kw)

    def equals(self, other: "DataFrame", ordered: bool = True) -> bool:
        return equals(self._table, other._to_env(self.env)._table,
                      ordered=ordered)

    def isin(self, other: "DataFrame") -> bool:
        """Row-subset test: every row of self appears in other."""
        diff = set_operation(self._table, other._to_env(self.env)._table,
                             "subtract")
        return diff.row_count == 0

    # -- missing data (reference frame.py:187-2421 breadth; pandas parity) --
    def _rebuild_cols(self, newcols: dict) -> "DataFrame":
        """New table from per-column results, re-attaching a hidden index
        column so the label index survives (pandas keeps the index through
        elementwise ops)."""
        for h in self._hidden():
            newcols[h] = self._table.column(h)
        return self._wrap(Table(newcols, self._table.env,
                                self._table.valid_counts), keep_index=True)

    def isna(self) -> "DataFrame":
        """Boolean frame: True where a value is missing (null or NaN)."""
        return self._rebuild_cols(
            {c: self[c].isna().column for c in self.columns})

    def notna(self) -> "DataFrame":
        return self._rebuild_cols(
            {c: self[c].notna().column for c in self.columns})

    # pandas/pycylon aliases (reference data/table.pyx isnull/notnull)
    isnull = isna
    notnull = notna

    def add_prefix(self, prefix: str) -> "DataFrame":
        """Rename every visible column to ``prefix + name`` (reference
        data/table.pyx add_prefix)."""
        return self.rename({c: prefix + c for c in self.columns})

    def add_suffix(self, suffix: str) -> "DataFrame":
        return self.rename({c: c + suffix for c in self.columns})

    def where(self, cond: "DataFrame | Series", other=None) -> "DataFrame":
        """Keep values where ``cond`` holds; elsewhere ``other`` (null when
        ``other`` is None) over a bool frame or a single bool Series.

        Divergence from pandas (intentional, pycylon-style): a Series
        ``cond`` is applied ROW-WISE to every column (what pandas spells
        ``where(cond, axis=0)``); pandas' default would align the Series
        on column labels, which is never useful for a row-predicate."""
        from .relational.common import valid_flag
        cols = {}
        for name in self.columns:
            col = self._table.column(name)
            c_ser = cond[name] if isinstance(cond, DataFrame) else cond
            flag = valid_flag(c_ser.column)
            if other is None:
                v = flag if col.validity is None else (col.validity & flag)
                cols[name] = Column(col.data, col.type, v, col.dictionary)
            else:
                s = Series(name, col, self.env, self._table.valid_counts)
                filled = s._fill_where(~flag, other)
                cols[name] = filled.column
        return self._rebuild_cols(cols)

    def to_pydict(self) -> dict:
        """Materialize as {column: list} (reference data/table.pyx
        to_pydict)."""
        return {c: list(self[c].to_numpy()) for c in self.columns}

    def to_string(self) -> str:
        return self.to_pandas().to_string()

    def show(self, n: int = 10) -> None:
        """Print the first n rows (reference data/table.pyx show /
        Table::PrintToOStream, table.hpp:96)."""
        print(self.head(n).to_pandas().to_string())

    def dropna(self, how: str = "any", subset=None) -> "DataFrame":
        """Drop rows with missing values (any/all over ``subset``)."""
        from .status import InvalidError as _IE
        if how not in ("any", "all"):
            raise _IE("how must be 'any' or 'all'")
        cols = list(subset) if subset is not None else self.columns
        keep = None
        for c in cols:
            ok = self[c].notna()
            keep = ok if keep is None else (
                (keep & ok) if how == "any" else (keep | ok))
        if keep is None:
            return self
        from .relational.common import valid_flag
        return self._wrap(filter_table(self._table, valid_flag(keep.column)),
                          keep_index=True)

    def fillna(self, value) -> "DataFrame":
        """Replace missing values (nulls and float NaNs) with ``value``.
        Columns whose dtype cannot hold ``value`` (e.g. a string column vs a
        numeric fill) are left unchanged — a documented deviation from
        pandas' object-dtype mixing, which fixed-width device columns cannot
        represent."""
        from .status import CylonTypeError
        cols = {}
        for name, c in self._table.columns.items():
            if name in self._hidden() or (
                    c.validity is None
                    and not str(c.data.dtype).startswith("float")):
                cols[name] = c
                continue
            s = Series(name, c, self.env, self._table.valid_counts)
            try:
                cols[name] = s.fillna(value).column
            except CylonTypeError:
                cols[name] = c
        return self._wrap(Table(cols, self._table.env,
                                self._table.valid_counts), keep_index=True)

    # -- elementwise frame arithmetic (pandas operator parity) -------------
    def _colwise(self, fn) -> "DataFrame":
        return self._rebuild_cols({c: fn(self[c]).column
                                   for c in self.columns})

    def _frame_op(self, other, op_name: str) -> "DataFrame":
        if isinstance(other, DataFrame):
            if other.columns != self.columns:
                raise InvalidError("frame op requires identical columns")
            return self._colwise(
                lambda s: getattr(s, op_name)(other[s.name]))
        return self._colwise(lambda s: getattr(s, op_name)(other))

    def __add__(self, o):
        return self._frame_op(o, "__add__")

    def __sub__(self, o):
        return self._frame_op(o, "__sub__")

    def __mul__(self, o):
        return self._frame_op(o, "__mul__")

    def __truediv__(self, o):
        return self._frame_op(o, "__truediv__")

    def __neg__(self):
        return self._colwise(lambda s: -s)

    def __abs__(self):
        return self._colwise(abs)

    def abs(self) -> "DataFrame":
        return self._colwise(abs)

    # -- row-wise host iteration (reference Row, row.hpp; frame.py parity) --
    def applymap(self, func) -> "DataFrame":
        """Elementwise python function over the data columns — host round
        trip by necessity (arbitrary python is not jittable); index labels
        are untouched, pandas-compatible."""
        pdf = self.to_pandas()
        mapped = pdf.map(func)
        if self._index is None:
            return DataFrame(mapped, env=self.env)
        idx = self._index_cols()
        out = DataFrame(mapped.reset_index(names=idx), env=self.env)
        return out.set_index(idx, drop=self._index_drop)

    def iterrows(self):
        """Host-side row iteration, pandas-compatible (reference Row
        iteration, row.hpp via table.cpp:892 Select)."""
        return self.to_pandas().iterrows()

    def itertuples(self, index: bool = True, name: str = "Cylon"):
        return self.to_pandas().itertuples(index=index, name=name)

    def row(self, i: int):
        """One global row as a :class:`~cylon_tpu.core.row.Row`."""
        from .core.row import Row
        return Row(self, i)

    # -- reductions over all columns ---------------------------------------
    def _agg_all(self, op: str):
        from .status import CylonTypeError
        import pandas as pd
        out = {}
        for name in self.columns:
            s = self[name]
            try:
                out[name] = getattr(s, op)()
            except CylonTypeError:
                continue  # column type doesn't support this reduction
        return pd.Series(out)

    def sum(self):
        return self._agg_all("sum")

    def min(self):
        return self._agg_all("min")

    def max(self):
        return self._agg_all("max")

    def count(self):
        return self._agg_all("count")

    def mean(self):
        return self._agg_all("mean")


class GroupByDataFrame:
    """Deferred groupby (reference frame.py:122 GroupByDataFrame): terminal
    aggregation methods run the distributed two-phase engine."""

    def __init__(self, df: DataFrame, by: list[str]):
        self._df = df
        self._by = by
        self._value_cols = [c for c in df.columns if c not in set(by)]

    def __getitem__(self, cols) -> "GroupByDataFrame":
        cols = [cols] if isinstance(cols, str) else list(cols)
        for c in cols:
            if c not in self._df.columns:
                raise CylonKeyError(f"no column {c!r}")
        g = GroupByDataFrame(self._df, self._by)
        g._value_cols = cols
        return g

    def _run(self, aggs) -> DataFrame:
        t = groupby_aggregate(self._df._table, self._by, aggs)
        return DataFrame(_table=t)

    def _all(self, op: str) -> DataFrame:
        from .core.dtypes import LogicalType
        # types via schema, NOT column(): column access would materialize a
        # DeferredTable join result and forfeit the fused groupby pushdown
        types = {f.name: f.type for f in self._df._table.schema}
        aggs = []
        for c in self._value_cols:
            if types[c] == LogicalType.STRING and op not in (
                    "count", "nunique", "min", "max"):
                continue
            aggs.append((c, op))
        if not aggs:
            raise InvalidError(f"no columns support {op!r}")
        out = self._run(aggs)
        # pandas-style: result columns keep the value column name
        ren = {f"{c}_{op}": c for c, _ in aggs}
        return DataFrame(_table=out._table.rename(ren))

    def sum(self) -> DataFrame:
        return self._all("sum")

    def count(self) -> DataFrame:
        return self._all("count")

    def min(self) -> DataFrame:
        return self._all("min")

    def max(self) -> DataFrame:
        return self._all("max")

    def mean(self) -> DataFrame:
        return self._all("mean")

    def var(self) -> DataFrame:
        return self._all("var")

    def std(self) -> DataFrame:
        return self._all("std")

    def nunique(self) -> DataFrame:
        return self._all("nunique")

    def median(self) -> DataFrame:
        return self._all("median")

    def quantile(self, q: float = 0.5) -> DataFrame:
        aggs = [(c, "quantile", q) for c in self._value_cols]
        out = self._run(aggs)
        ren = {f"{c}_quantile_{q:g}": c for c in self._value_cols}
        ren.update({f"{c}_quantile": c for c in self._value_cols})
        ren = {k: v for k, v in ren.items() if k in out.columns}
        return DataFrame(_table=out._table.rename(ren))

    def agg(self, spec) -> DataFrame:
        """pandas .agg spellings: a single op name ('sum'), a list of op
        names applied to every value column, {'col': 'sum'|['sum','mean']},
        or an explicit [(col, op), ...] list (ops may repeat across
        columns)."""
        if isinstance(spec, str):
            return self._all(spec)
        aggs = []
        if isinstance(spec, Mapping):
            for col, ops in spec.items():
                ops = [ops] if isinstance(ops, str) else list(ops)
                for op in ops:
                    aggs.append((col, op))
        elif spec and all(isinstance(a, str) for a in spec):
            aggs = [(c, op) for c in self._value_cols for op in spec]
        else:
            aggs = [tuple(a) for a in spec]
        if not aggs:
            raise InvalidError("no aggregations specified")
        return self._run(aggs)


def concat(objs: Sequence[DataFrame], env: CylonEnv | None = None) -> "DataFrame":
    """Row-wise concat (reference frame.py:2295)."""
    if not objs:
        raise InvalidError("concat of nothing")
    env = _resolve_env(objs[0].env, env)
    tables = [o._to_env(env)._table for o in objs]
    return DataFrame(_table=concat_tables(tables))


def read_pandas(df, env: CylonEnv | None = None) -> DataFrame:
    return DataFrame(df, env=env)
