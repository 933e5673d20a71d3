"""Device-resident Column.

TPU-native equivalent of the reference ``cylon::Column`` (cpp/src/cylon/
column.hpp:27, wrapping ``arrow::Array``).  Physical layout follows the GCylon
pattern (accelerator-resident, cpp/src/gcylon/gtable.hpp): a fixed-width
device array + an optional boolean validity array (bool array instead of the
Arrow bitmap — TPU vectors have no cheap bit addressing, and XLA fuses mask
ops for free).  Variable-width strings are dictionary-encoded: int32 codes on
device, the value table host-side (the reference likewise flattens non-fixed
keys to binary before hashing, util/flatten_array.cpp).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..status import CylonTypeError, InvalidError
from .dtypes import LogicalType, from_numpy_dtype, physical_np_dtype


class HashedStrings:
    """High-cardinality string 'dictionary': device codes are stable 64-bit
    value hashes (int64 bit-pattern) instead of sorted-dictionary indices.

    Rides the existing ``Column.dictionary`` slot so every column rebuild
    site propagates it untouched.  Semantics vs a real dictionary:

    * EQUALITY on codes is (probabilistically) value equality — joins,
      groupbys, set ops, unique and ==/!= filters are exact up to 64-bit
      hash collisions (birthday bound: ~3e-20·n² chance of any collision —
      ~0.3% at 100M distinct values); the reference compares flattened
      binary exactly (util/flatten_array.cpp), this path trades that for
      never building an n-entry dictionary.
    * ORDER of codes is NOT value order: lexical sorts, range compares and
      min/max on such columns raise (the caller sees a clear error, never
      a wrong answer).
    * decode goes through a lazily built hash->value map over the source
      values (only paid if the strings are actually materialized).

    Construction cost is one stable 64-bit hash per row
    (:func:`cylon_tpu.native.hash_strings` — native murmur64a when the
    toolchain is present).
    """

    __slots__ = ("_hashes", "_values", "_sorted")

    def __init__(self, hashes: np.ndarray, values: np.ndarray):
        self._hashes = hashes      # uint64, aligned with _values
        self._values = values      # object array of source strings
        self._sorted = None

    def _lookup(self):
        if self._sorted is None:
            order = np.argsort(self._hashes)
            hs = self._hashes[order]
            vs = self._values[order]
            keep = np.concatenate([[True], hs[1:] != hs[:-1]])
            self._sorted = (hs[keep], vs[keep])
        return self._sorted

    def take(self, codes: np.ndarray) -> np.ndarray:
        """Decode int64-bit-pattern codes to their string values."""
        hs, vs = self._lookup()
        u = np.asarray(codes).astype(np.int64).view(np.uint64)
        idx = np.clip(np.searchsorted(hs, u), 0, max(len(hs) - 1, 0))
        if len(hs) == 0:
            return np.asarray([""] * len(u), dtype=object)
        return vs[idx]

    def hash_values(self, values) -> np.ndarray:
        """int64-bit-pattern codes for new values (filter literals,
        dictionary-side re-encoding in joins)."""
        from .. import native
        return native.hash_strings(np.asarray(values, dtype=object)) \
            .view(np.int64)

    def merged_with(self, other: "HashedStrings") -> "HashedStrings":
        return HashedStrings(
            np.concatenate([self._hashes, other._hashes]),
            np.concatenate([self._values, other._values]))

    def __len__(self):  # distinct-count queries on the lookup
        return len(self._lookup()[0])


def hashed_codes(values: np.ndarray):
    """(codes int64, HashedStrings) for a host string/object array."""
    from .. import native
    hashes = native.hash_strings(np.asarray(values, dtype=object))
    return hashes.view(np.int64), HashedStrings(hashes, values)


class DecimalScale:
    """DECIMAL column metadata (rides the ``Column.dictionary`` slot like
    HashedStrings, so every column rebuild site propagates it untouched):
    device data is the UNSCALED int64 (value · 10^scale) — exact TPC-H
    money semantics for precision <= 18 (reference: Arrow decimal128
    comparators, arrow_comparator.cpp).  Equality/order on the scaled ints
    equals decimal equality/order at a COMMON scale, so joins, groupbys,
    sorts and filters all work on the physical column."""

    __slots__ = ("precision", "scale")

    def __init__(self, precision: int, scale: int):
        if precision > 18:
            raise CylonTypeError(
                f"decimal precision {precision} > 18 does not fit int64")
        self.precision = int(precision)
        self.scale = int(scale)

    def __eq__(self, other):
        return (isinstance(other, DecimalScale)
                and other.precision == self.precision
                and other.scale == self.scale)

    def __hash__(self):
        return hash((DecimalScale, self.precision, self.scale))

    def __repr__(self):  # pragma: no cover
        return f"DecimalScale({self.precision}, {self.scale})"

    def to_decimal(self, data: np.ndarray) -> np.ndarray:
        import decimal
        return np.asarray(
            [decimal.Decimal(int(v)).scaleb(-self.scale) for v in data],
            dtype=object)


class PassthroughValues:
    """Host-side passthrough 'dictionary' for values with no TPU device
    layout (variable-length lists): device data is int32 row codes into a
    host object array.  Carried through joins/filters/exchanges by the
    same code gathers strings use; NOT usable as a key (codes are row
    ids, not value-equal — key sites raise CylonTypeError)."""

    __slots__ = ("values",)

    def __init__(self, values: np.ndarray):
        self.values = np.asarray(values, dtype=object)

    def take(self, codes: np.ndarray) -> np.ndarray:
        n = len(self.values)
        if n == 0:
            return np.asarray([None] * len(codes), dtype=object)
        return self.values[np.clip(codes, 0, n - 1)]

    def __len__(self):
        return len(self.values)


class Column:
    __slots__ = ("data", "validity", "type", "dictionary", "bounds")

    def __init__(self, data, type: LogicalType, validity=None,
                 dictionary: Optional[np.ndarray] = None,
                 bounds: Optional[tuple] = None):
        self.data = data
        self.type = type
        self.validity = validity  # bool array, True = valid; None = all valid
        self.dictionary = dictionary  # host np.ndarray for STRING codes
        #: host-known (lo, hi) value bounds for integer columns, or None.
        #: Conservative: any subset/permutation of the values keeps them
        #: valid; ops that create new values must drop them.  Consulted by
        #: sort-operand packing: int64 keys within int32 range sort as ONE
        #: native operand (ops/pack.py narrow32).
        self.bounds = bounds
        if type == LogicalType.STRING and dictionary is None:
            raise InvalidError("STRING column requires a dictionary")
        if type == LogicalType.DECIMAL and not isinstance(dictionary,
                                                          DecimalScale):
            raise InvalidError("DECIMAL column requires a DecimalScale")
        if type == LogicalType.LIST and not isinstance(dictionary,
                                                       PassthroughValues):
            raise InvalidError("LIST column requires PassthroughValues")

    # -- construction ------------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, type: LogicalType | None = None) -> "Column":
        """Build a HOST column from a host array (data stays numpy — no
        device/backend is touched; ``Table`` factories place columns onto the
        env's devices explicitly, so ingestion never initializes the default
        backend).  Encodes strings/objects; NaN stays a float payload,
        matching pandas semantics."""
        arr = np.asarray(arr)
        if arr.dtype.kind in ("U", "S", "O"):
            return Column._encode_strings(arr)
        lt = type or from_numpy_dtype(arr.dtype)
        phys = physical_np_dtype(lt)
        if arr.dtype.kind == "M":
            # normalize any pandas resolution (s/ms/us) to ns before bitview
            arr = arr.astype("datetime64[ns]").astype("int64", copy=False)
        elif arr.dtype.kind == "m":
            arr = arr.astype("timedelta64[ns]").astype("int64", copy=False)
        arr = arr.astype(phys, copy=False)
        bounds = None
        if arr.dtype.kind in ("i", "u") and arr.size:
            bounds = (int(arr.min()), int(arr.max()))
        return Column(arr, lt, bounds=bounds)

    @staticmethod
    def from_scaled_ints(values: np.ndarray, scale: int,
                         precision: int | None = None,
                         validity: np.ndarray | None = None) -> "Column":
        """The typed way in for DECIMAL: a HOST column from the UNSCALED
        integers (``value * 10**scale``, e.g. cents for scale 2) - no
        Python object a row, so 30M values cost what an int64 column
        costs.  ``precision`` is the declared one (``decimal(15,2)`` -> 15;
        default: the digits of the largest value, as the object path
        sets it); bounds are taken from the data as for an integer
        column, which is what lets arithmetic on the column prove its
        results fit (series.Series._decimal_arith)."""
        data = np.ascontiguousarray(values, dtype=np.int64)
        if validity is not None:
            data = np.where(validity, data, 0)
        bounds = (int(data.min()), int(data.max())) if data.size else None
        digits = len(str(max(abs(bounds[0]), abs(bounds[1])))) \
            if bounds else 1
        if precision is not None and digits > precision:
            raise CylonTypeError(
                f"decimal({precision},{scale}) cannot hold a value of "
                f"{digits} digits")
        return Column(data, LogicalType.DECIMAL, validity,
                      DecimalScale(precision or digits, scale),
                      bounds=bounds)

    @staticmethod
    def from_dictionary(codes: np.ndarray, values,
                        validity: np.ndarray | None = None) -> "Column":
        """A HOST STRING column from dictionary codes and their value table
        (pandas ``Categorical`` codes / categories, an Arrow dictionary):
        re-coded onto the SORTED unique values - code order == lexical
        order, the invariant of every string column - at the cost of one
        int32 gather a row, with no string touched a row."""
        values = np.asarray([str(v) for v in values], dtype=object)
        uniq, remap = np.unique(values, return_inverse=True)
        codes = np.asarray(codes)
        if len(values):
            codes = remap.astype(np.int32)[np.clip(codes, 0, len(values) - 1)]
        return Column(codes.astype(np.int32, copy=False), LogicalType.STRING,
                      validity, np.asarray(uniq, dtype=object))

    @staticmethod
    def _decimal_from_objects(arr: np.ndarray, mask: np.ndarray) -> "Column":
        """Object array of decimal.Decimal -> scaled-int64 DECIMAL column
        (exact for precision <= 18; reference: decimal128 comparators)."""
        import decimal
        vals = [v for v, m in zip(arr, mask) if not m]
        try:
            # TypeError also covers non-finite Decimals (NaN/Infinity),
            # whose as_tuple().exponent is a str
            scale = max((-v.as_tuple().exponent for v in vals), default=0)
        except (AttributeError, TypeError) as e:
            raise CylonTypeError(
                "mixed or non-finite decimal column; cast uniformly "
                "before ingest") from e
        scale = max(scale, 0)
        data = np.zeros(len(arr), np.int64)
        for i, (v, m) in enumerate(zip(arr, mask)):
            if not m:
                try:
                    data[i] = int(decimal.Decimal(v).scaleb(scale))
                except (decimal.InvalidOperation, TypeError,
                        ValueError) as e:
                    raise CylonTypeError(
                        "mixed decimal column; cast uniformly before "
                        "ingest") from e
        # tight precision (actual digit count): leaves headroom for later
        # 10^Δ rescales against finer-scaled partners (the 18 cap is the
        # int64 representation's, not each column's)
        return Column.from_scaled_ints(data, scale,
                                       validity=~mask if mask.any() else None)

    @staticmethod
    def _list_passthrough(arr: np.ndarray, mask: np.ndarray) -> "Column":
        """Object array of lists -> host passthrough column (carried
        through joins by code gathers; not usable as a key)."""
        codes = np.arange(len(arr), dtype=np.int32)
        validity = ~mask if mask.any() else None
        return Column(codes, LogicalType.LIST, validity,
                      PassthroughValues(arr),
                      bounds=(0, max(len(arr) - 1, 0)))

    @staticmethod
    def _encode_strings(arr: np.ndarray) -> "Column":
        if arr.dtype.kind == "S":  # binary: decode, don't repr-mangle
            arr = np.char.decode(arr, "utf-8")
        if arr.dtype == object:
            # pd.isna covers None, float NaN, pd.NA and NaT — a hand-rolled
            # None/NaN check silently stringifies pd.NA (pandas StringDtype
            # nulls) into the literal "<NA>".  pd.isna on a cell holding a
            # LIST returns an array — probe for nested values first.
            import pandas as pd
            import decimal

            def null_scalar(v):
                # list cells make pd.isna return an ARRAY — guard them
                if isinstance(v, (list, np.ndarray)):
                    return False
                return bool(pd.isna(v))   # None, NaN, pd.NA, NaT

            probe = next((v for v in arr if not null_scalar(v)), None)
            if isinstance(probe, (list, np.ndarray)):
                mask = np.asarray([null_scalar(v) for v in arr], bool)
                return Column._list_passthrough(arr, mask)
            mask = np.asarray(pd.isna(arr), bool)
            if isinstance(probe, decimal.Decimal):
                return Column._decimal_from_objects(arr, mask)
        else:
            mask = np.zeros(len(arr), bool)
        safe = np.where(mask, "", arr.astype(object)) if mask.any() else arr

        import decimal

        def as_str(v):
            # documented rejection (SURVEY C6: the reference's comparators
            # span every Arrow type incl. lists, join_test.cpp:124): struct
            # values have no TPU device layout OR passthrough mode here —
            # refuse loudly instead of silently stringifying a wrong
            # answer.  (Lists take the passthrough path above; decimals
            # the scaled-int64 path.)
            if isinstance(v, (list, tuple, dict, np.ndarray)):
                raise CylonTypeError(
                    "struct/mixed nested columns are not supported on the "
                    "TPU device layout; explode or serialize them before "
                    "ingest")
            if isinstance(v, decimal.Decimal):
                raise CylonTypeError(
                    "mixed decimal/str column; cast uniformly before "
                    "ingest")
            if isinstance(v, (bytes, np.bytes_)):
                return v.decode("utf-8", "replace")
            return str(v)

        if safe.dtype.kind == "U":
            values = safe.astype(object)
        elif all(isinstance(v, str) for v in safe[:64]):
            # object arrays from pandas are usually already str (np.str_
            # included) — probe a prefix, stringify only the exceptions
            values = np.asarray(
                [v if isinstance(v, str) else as_str(v) for v in safe],
                dtype=object)
        else:
            values = np.asarray([as_str(v) for v in safe], dtype=object)
        validity = ~mask if mask.any() else None
        # crossover heuristic: a sampled distinct-ratio estimate decides
        # between the sorted dictionary (order-isomorphic codes — lexical
        # sorts/compares work) and the hashed-codes path (HashedStrings:
        # no n-entry dictionary is ever built; equality-only semantics).
        # Reference analog: flatten-then-hash of non-fixed keys
        # (util/flatten_array.cpp + util/murmur3.cpp).
        from .. import config
        n = len(values)
        # x64 opt-out downcasts 8-byte transfers: 32-bit hash equality
        # would collide at birthday rates, so the crossover requires x64
        if n >= config.STRING_HASH_MIN_ROWS and config.X64_ENABLED:
            samp = values[::max(n // 65536, 1)][:65536]
            if len(np.unique(samp)) >= config.STRING_HASH_RATIO * len(samp):
                codes, lookup = hashed_codes(values)
                return Column(codes, LogicalType.STRING, validity, lookup)
        # sorted dictionary so code order == lexical order: sorts/joins on
        # codes are exact on the decoded values.  pd.factorize(sort=True)
        # is the C-speed np.unique(return_inverse) (several x faster on
        # object arrays — the ingest hot loop at TPC-H scale).
        import pandas as pd
        codes, uniques = pd.factorize(values, sort=True)
        dictionary = np.asarray(uniques, dtype=object)
        return Column(codes.astype(np.int32), LogicalType.STRING, validity,
                      dictionary)

    # -- properties --------------------------------------------------------
    def __len__(self) -> int:
        return int(self.data.shape[0])

    @property
    def has_nulls(self) -> bool:
        return self.validity is not None

    def with_data(self, data, validity="__same__") -> "Column":
        v = self.validity if validity == "__same__" else validity
        return Column(data, self.type, v, self.dictionary)

    # -- materialization ---------------------------------------------------
    def to_numpy(self, n: int | None = None) -> np.ndarray:
        """Decode to a host array of length n (valid prefix)."""
        data = np.asarray(self.data)[: n if n is not None else len(self)]
        valid = (np.asarray(self.validity)[: len(data)]
                 if self.validity is not None else None)
        if self.type == LogicalType.DECIMAL:
            out = self.dictionary.to_decimal(data)
            if valid is not None:
                out[~valid] = None
            return out
        if self.type == LogicalType.LIST:
            out = np.asarray(self.dictionary.take(data), dtype=object)
            if valid is not None:
                out = out.copy()
                out[~valid] = None
            return out
        if self.type == LogicalType.STRING:
            if isinstance(self.dictionary, HashedStrings):
                out = self.dictionary.take(data)
            else:
                out = self.dictionary[
                    np.clip(data, 0, len(self.dictionary) - 1)]
            out = np.asarray(out).astype(object)
            if valid is not None:
                out[~valid] = None
            return out
        if self.type == LogicalType.DATE64:
            out = data.astype("datetime64[ns]")
        elif self.type == LogicalType.TIMEDELTA:
            out = data.astype("timedelta64[ns]")
        else:
            out = data.astype(np.dtype(self.type.value), copy=False)
        if valid is not None:
            if out.dtype.kind == "f":
                out = out.copy()
                out[~valid] = np.nan
            else:
                out = out.astype(object)
                out[~valid] = None
        return out

    def cast(self, lt: LogicalType) -> "Column":
        if self.type == LogicalType.STRING or lt == LogicalType.STRING:
            raise CylonTypeError("cast to/from string not supported on device")
        phys = physical_np_dtype(lt)
        keep = (self.bounds is not None and phys.kind in ("i", "u")
                and np.can_cast(np.min_scalar_type(self.bounds[0]), phys)
                and np.can_cast(np.min_scalar_type(self.bounds[1]), phys))
        return Column(self.data.astype(phys), lt, self.validity,
                      bounds=self.bounds if keep else None)
