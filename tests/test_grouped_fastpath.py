"""The grouped-input groupby fast path (join/sort output carries
``grouped_by``: boundary-flag group ids, no shuffle, no rank sort) must give
identical results to the general path — checked against the pandas oracle."""

import numpy as np
import pandas as pd

import cylon_tpu as ct
from cylon_tpu.relational import groupby_aggregate, join_tables, sort_table

from utils import assert_table_matches


def test_join_then_groupby_matches_oracle(env, rng):
    n = 200
    ldf = pd.DataFrame({"k": rng.integers(0, 20, n), "a": rng.random(n)})
    rdf = pd.DataFrame({"k": rng.integers(0, 20, n // 2),
                        "b": rng.random(n // 2)})
    lt = ct.Table.from_pandas(ldf, env)
    rt = ct.Table.from_pandas(rdf, env)
    j = join_tables(lt, rt, "k", "k", how="inner")
    assert j.grouped_by == ("k",)
    g = groupby_aggregate(j, "k", [("a", "sum"), ("b", "mean"),
                                   ("a", "count")])
    exp = (ldf.merge(rdf, on="k", how="inner")
           .groupby("k", as_index=False)
           .agg(a_sum=("a", "sum"), b_mean=("b", "mean"),
                a_count=("a", "count")))
    assert_table_matches(g, exp)


def test_sort_then_groupby_matches_oracle(env, rng):
    n = 300
    df = pd.DataFrame({"k": rng.integers(0, 12, n).astype(float),
                       "v": rng.standard_normal(n)})
    # sprinkle nulls into the key to hit the null-aware boundary compare
    df.loc[df.index % 17 == 0, "k"] = None
    t = ct.Table.from_pandas(df, env)
    s = sort_table(t, "k")
    assert s.grouped_by == ("k",)
    g = groupby_aggregate(s, "k", [("v", "sum"), ("v", "max")])
    exp = (df.groupby("k", as_index=False, dropna=False)
           .agg(v_sum=("v", "sum"), v_max=("v", "max")))
    assert_table_matches(g, exp)


def test_grouped_flag_cleared_by_other_ops(env):
    df = pd.DataFrame({"k": [1, 1, 2, 2], "v": [1.0, 2.0, 3.0, 4.0]})
    t = ct.Table.from_pandas(df, env)
    s = sort_table(t, "k")
    assert s.grouped_by == ("k",)
    # projection rebuilds a Table -> metadata conservatively dropped
    assert s.project(["k"]).grouped_by is None
    # groupby on different keys ignores the metadata
    g = groupby_aggregate(s, "v", [("k", "count")])
    assert g.row_count == 4


def test_float_keys_grouped_path_nan_and_negzero(env):
    df = pd.DataFrame({"k": [0.0, -0.0, 1.5, np.nan, np.nan, 1.5],
                       "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]})
    t = ct.Table.from_pandas(df, env)
    s = sort_table(t, "k")
    g = groupby_aggregate(s, "k", [("v", "sum")])
    exp = df.groupby("k", as_index=False, dropna=False).agg(
        v_sum=("v", "sum"))
    assert_table_matches(g, exp)


def test_narrow_key_join_matches_wide(env, rng):
    """int64 keys within int32 range pack to one sort operand — results must
    match a join on keys forced outside the narrow range."""
    n = 100
    base = rng.integers(0, 50, n)
    ldf = pd.DataFrame({"k": base, "a": rng.random(n)})
    rdf = pd.DataFrame({"k": rng.integers(0, 50, n), "b": rng.random(n)})
    lt = ct.Table.from_pandas(ldf, env)
    rt = ct.Table.from_pandas(rdf, env)
    j = join_tables(lt, rt, "k", "k", how="outer")
    exp = ldf.merge(rdf, on="k", how="outer")
    assert_table_matches(j, exp)
    # same data shifted beyond int32 -> wide (hi, lo) packing path
    big = np.int64(1) << 40
    ldf2 = ldf.assign(k=ldf.k + big)
    rdf2 = rdf.assign(k=rdf.k + big)
    j2 = join_tables(ct.Table.from_pandas(ldf2, env),
                     ct.Table.from_pandas(rdf2, env), "k", "k", how="outer")
    assert_table_matches(j2, ldf2.merge(rdf2, on="k", how="outer"))


def test_grouped_uint64_wide_keys_and_values(env4, rng):
    """uint64 keys/values beyond int32 range through the grouped fast path
    (regression: the u32 lane split must mask with the source dtype, and
    wide values must keep 2-lane sum prefixes)."""
    n = 256
    base = np.uint64(1) << np.uint64(33)
    kdf = pd.DataFrame({"k": (rng.integers(0, 6, n).astype(np.uint64) + base),
                        "a": rng.integers(0, 1 << 40, n).astype(np.uint64)})
    rdf = pd.DataFrame({"k": (rng.integers(0, 6, n // 2).astype(np.uint64)
                              + base),
                        "b": rng.integers(0, 100, n // 2).astype(np.uint64)})
    lt = ct.Table.from_pandas(kdf, env4)
    rt = ct.Table.from_pandas(rdf, env4)
    j = join_tables(lt, rt, "k", "k", how="inner")
    g = groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum"),
                                   ("a", "count")])
    exp = (kdf.merge(rdf, on="k", how="inner")
           .groupby("k", as_index=False)
           .agg(a_sum=("a", "sum"), b_sum=("b", "sum"),
                a_count=("a", "count")))
    assert_table_matches(g, exp)
