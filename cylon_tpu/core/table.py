"""Device-resident Table.

TPU-native equivalent of ``cylon::Table`` (reference cpp/src/cylon/table.hpp:46
— a ``shared_ptr<arrow::Table>`` + context) in the GCylon accelerator-resident
style (cpp/src/gcylon/gtable.hpp: data stays in device memory, the host only
orchestrates).  Layout:

* every column is a global ``jax.Array`` of identical length ``W * cap``,
  row-sharded over the env mesh (``P(ROW_AXIS)``);
* shard ``i`` holds ``valid_counts[i] <= cap`` real rows as a prefix, the rest
  is padding — XLA collectives are static-shape, so capacity-padding + a
  row-count sidecar replaces the reference's variable-size Arrow buffer
  serializer (serialize/table_serialize.hpp:23, SURVEY.md §5.8);
* global row order == concatenation of shard valid prefixes in rank order
  (the same contract the reference's order-preserving all-to-all maintains,
  table.cpp:182-190).

A local table is the world-size-1 special case: one shard, zero padding.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Sequence

import jax
import numpy as np

from ..ctx.context import CylonEnv, LocalConfig
from ..status import CylonKeyError, InvalidError
from ..utils import timing
from .column import Column
from .dtypes import Field, LogicalType

_default_env: CylonEnv | None = None


def default_env() -> CylonEnv:
    global _default_env
    if _default_env is None:
        _default_env = CylonEnv(LocalConfig())
    return _default_env


class Table:
    # __weakref__: the HBM ledger (exec/memory.register_table) anchors
    # byte registrations to table lifetime via weakref.finalize
    __slots__ = ("_cols", "_env", "_valid", "grouped_by", "__weakref__")

    def __init__(self, cols: Mapping[str, Column], env: CylonEnv | None,
                 valid_counts: np.ndarray | None = None):
        self._cols: dict[str, Column] = dict(cols)
        self._env = env or default_env()
        #: names of key columns this table is known to be GROUPED by: equal
        #: keys are contiguous within each shard and co-located across
        #: shards.  Set by ops that establish the property (join output,
        #: global sort, groupby output); every other constructor path leaves
        #: it None.  Lets groupby skip its shuffle + rank sort.
        self.grouped_by: tuple | None = None
        n = None
        for c in self._cols.values():
            if n is None:
                n = len(c)
            elif len(c) != n:
                raise InvalidError("column length mismatch")
        n = n or 0
        w = self._env.world_size
        if valid_counts is None:
            if n % w:
                raise InvalidError(f"rows {n} not divisible by world {w}")
            valid_counts = np.full(w, n // w, dtype=np.int64)
        self._valid = np.asarray(valid_counts, dtype=np.int64)
        if self._valid.shape != (w,):
            raise InvalidError("valid_counts must have one entry per rank")

    # -- construction ------------------------------------------------------
    @staticmethod
    def from_pydict(data: Mapping[str, np.ndarray], env: CylonEnv | None = None) -> "Table":
        """Values are host arrays, or already-typed HOST columns
        (``Column.from_scaled_ints``, ``Column.from_dictionary``), which
        pass as they are."""
        env = env or default_env()
        arrays = {k: v if isinstance(v, Column) else np.asarray(v)
                  for k, v in data.items()}
        with timing.region(
                "table.from_pydict",
                rows=len(next(iter(arrays.values()))) if arrays else 0,
                bytes=sum(int((a.data if isinstance(a, Column) else a).nbytes)
                          for a in arrays.values())):
            cols = {k: a if isinstance(a, Column) else Column.from_numpy(a)
                    for k, a in arrays.items()}
            return _ingest(cols, env)

    @staticmethod
    def from_pandas(df, env: CylonEnv | None = None) -> "Table":
        env = env or default_env()
        cols = {str(k): _column_from_series(df[k]) for k in df.columns}
        return _ingest(cols, env)

    @staticmethod
    def from_arrow(at, env: CylonEnv | None = None) -> "Table":
        """From a pyarrow.Table via direct buffer conversion — no pandas
        object round trip (reference Table::FromArrowTable, table.hpp:61;
        conversion rules in core/arrow_interop.py)."""
        from .arrow_interop import table_from_arrow
        return table_from_arrow(at, env)

    @staticmethod
    def from_numpy(names: Sequence[str], arrays: Sequence[np.ndarray],
                   env: CylonEnv | None = None) -> "Table":
        return Table.from_pydict(dict(zip(names, arrays)), env)

    @staticmethod
    def from_host_columns(cols: Mapping[str, Column],
                          env: CylonEnv | None = None) -> "Table":
        """Place already-typed HOST columns (numpy data/validity, logical
        type and dictionary preserved) onto the env — the dtype-faithful
        ingest path (no pandas object round-trip)."""
        env = env or default_env()
        return _ingest(dict(cols), env)

    # -- schema ------------------------------------------------------------
    @property
    def env(self) -> CylonEnv:
        return self._env

    @property
    def column_names(self) -> list[str]:
        return list(self._cols)

    @property
    def columns(self) -> dict[str, Column]:
        return self._cols

    @property
    def column_count(self) -> int:
        return len(self._cols)

    @property
    def row_count(self) -> int:
        """Global (world-wide) valid row count."""
        return int(self._valid.sum())

    @property
    def valid_counts(self) -> np.ndarray:
        return self._valid

    @property
    def capacity(self) -> int:
        """Per-shard padded capacity."""
        if not self._cols:
            return 0
        return len(next(iter(self._cols.values()))) // self._env.world_size

    @property
    def schema(self) -> list[Field]:
        return [Field(k, c.type, c.has_nulls) for k, c in self._cols.items()]

    def column(self, name: str) -> Column:
        try:
            return self._cols[name]
        except KeyError:
            raise CylonKeyError(f"no column {name!r}; have {self.column_names}")

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    # -- projections (host-side metadata ops, zero device work) ------------
    def project(self, names: Iterable[str]) -> "Table":
        return Table({n: self.column(n) for n in names}, self._env, self._valid)

    def drop(self, names: Iterable[str]) -> "Table":
        drop = set(names)
        return Table({k: v for k, v in self._cols.items() if k not in drop},
                     self._env, self._valid)

    def rename(self, mapping: Mapping[str, str]) -> "Table":
        return Table({mapping.get(k, k): v for k, v in self._cols.items()},
                     self._env, self._valid)

    def with_columns(self, extra: Mapping[str, Column]) -> "Table":
        cols = dict(self._cols)
        cols.update(extra)
        return Table(cols, self._env, self._valid)

    # -- materialization ---------------------------------------------------
    def _concat_live(self, host, valid):
        w = self._env.world_size
        cap = self.capacity
        sl = [slice(i * cap, i * cap + int(self._valid[i])) for i in range(w)]
        data = np.concatenate([host[s] for s in sl]) if sl else host[:0]
        vcat = (np.concatenate([valid[s] for s in sl])
                if valid is not None else None)
        return data, vcat

    def host_column(self, name: str):
        """(data, validity) host arrays of one column's live rows in global
        order (shard valid prefixes concatenated) — multi-host aware.  For
        whole-table materialization use :meth:`host_columns` (ONE batched
        device fetch instead of per-column round-trips)."""
        from ..utils.host import host_arrays
        c = self.column(name)
        host, valid = host_arrays([c.data, c.validity])
        return self._concat_live(host, valid)

    def host_columns(self):
        """{name: (data, validity)} live-row host arrays for every column
        in ONE batched device fetch (sequential first fetches pay the
        device-to-host latency each; utils.host.host_arrays overlaps
        them)."""
        from ..utils.host import host_arrays
        flat = []
        for c in self._cols.values():
            flat.append(c.data)
            flat.append(c.validity)
        pulled = host_arrays(flat)
        return {k: self._concat_live(pulled[2 * i], pulled[2 * i + 1])
                for i, k in enumerate(self._cols)}

    def to_pandas(self):
        import pandas as pd
        out = {}
        hosts = self.host_columns()
        for k, c in self._cols.items():
            data, vcat = hosts[k]
            out[k] = Column(data, c.type, vcat, c.dictionary).to_numpy(len(data))
        return pd.DataFrame(out)

    def to_arrow(self):
        from .arrow_interop import table_to_arrow
        return table_to_arrow(self)

    def to_pylist(self) -> list[dict]:
        return self.to_pandas().to_dict("records")

    def __repr__(self) -> str:  # pragma: no cover
        return (f"Table(rows={self.row_count}, cols={self.column_names}, "
                f"world={self._env.world_size}, cap={self.capacity})")


class DeferredTable(Table):
    """A Table whose columns materialize lazily on first data access.

    The TPU analog of the reference's streaming operator DAG
    (cpp/src/cylon/ops/, SURVEY §2 C9): an upstream operator (join) may
    hand its *pre-materialization state* to a compatible downstream
    consumer (groupby pushdown, relational/fused.py) without ever paying
    for the intermediate table; any other access runs the deferred
    materialization transparently.

    Schema queries (``column_names``/``schema``/``capacity``/counts)
    answer from stored metadata so DataFrame-level bookkeeping does not
    force materialization; ``column()``/``columns`` do."""

    __slots__ = ("_thunk", "_cap", "_meta", "op_state", "_counts_thunk")

    def __init__(self, env, valid_counts, capacity: int | None, thunk,
                 meta, op_state=None, counts_thunk=None):
        """``meta`` = (names, types, dicts, has_nulls) tuples parallel to
        the eventual columns; ``thunk()`` -> dict[str, Column]; ``op_state``
        is consumed by fused downstream operators (cleared on
        materialization).

        ``counts_thunk`` (with ``valid_counts=None``): the per-shard output
        counts are still on device — the producer dispatched its count
        phase but did NOT pull the result, so the NEXT operator's dispatch
        can be enqueued before this one's host sync (the pipelined piece
        loop's one-deep software pipeline).  First access of
        ``valid_counts``/``row_count``/``capacity`` pulls; a fused consumer
        that drains ``op_state`` never does."""
        self._thunk = None
        self._counts_thunk = None
        if valid_counts is None:
            if counts_thunk is None:
                raise InvalidError("DeferredTable needs valid_counts or "
                                   "counts_thunk")
            valid_counts = np.zeros(
                (env or default_env()).world_size, np.int64)
        super().__init__({}, env, valid_counts)
        self._counts_thunk = counts_thunk
        self._cap = None if capacity is None else int(capacity)
        self._meta = meta
        self._thunk = thunk
        self.op_state = op_state

    # _valid shadows the Table slot: reads pull the pending device counts
    @property
    def _valid(self):
        if self._counts_thunk is not None:
            th, self._counts_thunk = self._counts_thunk, None
            Table._valid.__set__(self, np.asarray(th(), np.int64))
        return Table._valid.__get__(self)

    @_valid.setter
    def _valid(self, v):
        self._counts_thunk = None
        Table._valid.__set__(self, v)

    # _cols shadows the Table slot: reads trigger materialization
    @property
    def _cols(self):
        if self._thunk is not None:
            thunk, self._thunk = self._thunk, None
            # drop the fused-consumer state BEFORE materializing: it pins
            # N-length device buffers the thunk never reads, and peak HBM
            # during the expansion is the binding constraint
            self.op_state = None
            out = thunk()
            if isinstance(out, Table):
                # OOM-fallback protocol: the thunk re-ran the whole
                # operator down a streaming path and produced a fresh
                # Table — adopt its layout (per-shard counts/capacity may
                # differ from the deferred prediction; global rows match)
                Table._cols.__set__(self, dict(out.columns))
                self._valid = out.valid_counts
                self._cap = out.capacity
                self.grouped_by = out.grouped_by
            else:
                Table._cols.__set__(self, dict(out))
        return Table._cols.__get__(self)

    @_cols.setter
    def _cols(self, v):
        Table._cols.__set__(self, v)

    @property
    def materialized(self) -> bool:
        return self._thunk is None

    # -- schema without materialization ------------------------------------
    @property
    def column_names(self) -> list[str]:
        return list(self._meta[0])

    @property
    def column_count(self) -> int:
        return len(self._meta[0])

    @property
    def capacity(self) -> int:
        if self._cap is None:
            # capacity prediction pending on the device counts (lazy-count
            # deferred join): pull and bucket exactly like the producer
            # would have
            from .. import config
            counts = self._valid
            self._cap = config.pow2ceil(int(counts.max())
                                        if counts.size else 1)
        return self._cap

    @property
    def schema(self) -> list[Field]:
        return [Field(n, t, hn) for n, t, hn in
                zip(self._meta[0], self._meta[1], self._meta[3])]

    def __contains__(self, name: str) -> bool:
        return name in self._meta[0]


def _column_from_series(s) -> Column:
    """pandas Series -> HOST Column, nullable-extension-dtype aware: masked
    numeric/boolean dtypes (Int64/Float64/boolean, with .numpy_dtype) keep
    their numeric payload + a validity mask instead of collapsing to an
    object array of pd.NA (which would stringify); everything else takes
    the plain to_numpy path (object/str columns dictionary-encode with a
    pd.isna mask in Column._encode_strings)."""
    import pandas as pd
    if isinstance(s.dtype, pd.CategoricalDtype) \
            and s.cat.categories.dtype.kind in ("O", "U", "T"):
        codes = s.cat.codes.to_numpy()
        return Column.from_dictionary(
            codes, s.cat.categories,
            (codes >= 0) if (codes < 0).any() else None)
    npdt = getattr(s.dtype, "numpy_dtype", None)
    if npdt is not None and npdt.kind in ("i", "u", "f", "b"):
        mask = np.asarray(s.isna(), bool)
        if mask.any():
            vals = s.to_numpy(dtype=npdt, na_value=0)
            col = Column.from_numpy(vals)
            return Column(col.data, col.type, ~mask, col.dictionary,
                          bounds=col.bounds)
        return Column.from_numpy(s.to_numpy(dtype=npdt))
    return Column.from_numpy(s.to_numpy())


def _put(host: np.ndarray, sharding):
    """Place a host array under a sharding.  device_put in single-controller
    mode; in multi-controller (jax.distributed) mode each process holds the
    same full host copy and materializes only its addressable shards
    (SPMD ingest — the reference's per-rank partition reads).

    This is the documented host→device UPLOAD boundary (trace-safety,
    docs/trace_safety.md): device_put/make_array_from_callback are
    explicit transfers, permitted under every transfer-guard level the
    test rig uses; the matching device→host boundary is the
    utils/host.py pull funnel."""
    import jax as _jax
    if _jax.process_count() > 1:
        return _jax.make_array_from_callback(host.shape, sharding,
                                             lambda idx: host[idx])
    return _jax.device_put(host, sharding)


def _place_local(cols: dict[str, Column], env: CylonEnv) -> dict[str, Column]:
    """Place host-built columns onto the env's (single) device — only the
    env's devices are ever touched, never the process default backend (the
    round-1 multichip dryrun died on exactly that leak)."""
    sharding = env.sharding()
    out = {}
    for k, c in cols.items():
        data = _put(np.asarray(c.data), sharding)
        v = (_put(np.asarray(c.validity), sharding)
             if c.validity is not None else None)
        out[k] = Column(data, c.type, v, c.dictionary, bounds=c.bounds)
    return out


def _ingest(cols: dict[str, Column], env: CylonEnv) -> Table:
    """Ingest dispatch — the shape-family canonicalization gate
    (exec/compiler.family_cap, docs/robustness.md "Compile lifecycle").

    Single-controller tables historically placed EXACT shapes
    (``_place_local``), so every distinct tenant row count compiled its
    own program family — compile cost O(tenants).  With shape families
    armed (the default) a world-1 ingest whose row count is not already
    its own family representative routes through :func:`_distribute`,
    which pow2-pads the capacity with a masked validity tail — exactly
    what multi-rank ingest always did — so near-miss row counts share
    one compiled program per plan shape, bit- and order-equal.
    ``CYLON_TPU_SHAPE_FAMILIES=0`` (and already-canonical or empty
    ingests) keep the zero-copy exact placement."""
    n = len(next(iter(cols.values()))) if cols else 0
    with timing.span("table.upload", rows=n):
        if env.world_size == 1:
            from ..exec.compiler import family_cap
            if family_cap(n) == n:
                return Table(_place_local(cols, env), env)
        return _distribute(cols, env)


def _distribute(cols: dict[str, Column], env: CylonEnv) -> Table:
    """Split host-built columns into W contiguous row blocks, pad each to the
    common capacity, and place them sharded on the mesh.  This is the
    single-controller analog of per-rank partition ingestion (reference:
    each rank reads its own partition, docs/docs/arch.md:42-47)."""
    from .. import config
    n = len(next(iter(cols.values()))) if cols else 0
    w = env.world_size
    chunk = -(-n // w)  # contiguous rows per rank (last ranks may get fewer)
    # pow2-bucketed capacity: bounds the family of compiled shapes across
    # ingests of varying row counts
    cap = config.pow2ceil(chunk)
    # the canonicalization decision is a pure function of (rows, world) —
    # rank-uniform, no vote — recorded on the active plan node (no-op
    # without a profile) so EXPLAIN output shows the family bucket
    from ..obs.plan import annotate
    annotate(shape_family=int(cap), ingest_rows=int(n))
    valid = np.asarray([max(0, min(chunk, n - i * chunk)) for i in range(w)],
                       np.int64)
    sharding = env.sharding()
    out = {}
    for k, c in cols.items():
        host = np.asarray(c.data)
        padded = np.zeros((w * cap,) + host.shape[1:], host.dtype)
        vhost = np.asarray(c.validity) if c.validity is not None else None
        vpad = np.zeros(w * cap, bool) if vhost is not None else None
        for i in range(w):
            m = int(valid[i])
            if m:
                padded[i * cap: i * cap + m] = host[i * chunk: i * chunk + m]
                if vpad is not None:
                    vpad[i * cap: i * cap + m] = vhost[i * chunk: i * chunk + m]
        data = _put(padded, sharding)
        v = _put(vpad, sharding) if vpad is not None else None
        # padding rows are zeros — covered by widening bounds to include 0
        b = c.bounds
        if b is not None:
            b = (min(b[0], 0), max(b[1], 0))
        out[k] = Column(data, c.type, v, c.dictionary, bounds=b)
    return Table(out, env, valid)
