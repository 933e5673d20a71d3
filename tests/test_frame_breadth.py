"""Frame breadth added for reference parity (frame.py:187-2421): index
drop semantics + propagation, dropna/fillna/isna/notna, frame arithmetic,
applymap/iterrows, Row/Scalar."""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.status import CylonKeyError

from utils import assert_frames_equal


@pytest.fixture
def data(rng):
    df = pd.DataFrame({"id": np.arange(20),
                       "v": rng.standard_normal(20),
                       "w": rng.integers(0, 5, 20).astype(float)})
    df.loc[df.index % 4 == 0, "v"] = np.nan
    return df


def test_set_index_drop_semantics(env, data):
    d = ct.DataFrame(data, env=env)
    di = d.set_index("id")            # pandas default: drop=True
    assert "id" not in di.columns
    with pytest.raises(CylonKeyError):
        di["id"]
    pd.testing.assert_frame_equal(di.to_pandas(), data.set_index("id"),
                                  check_dtype=False)
    dk = d.set_index("id", drop=False)
    assert "id" in dk.columns
    pd.testing.assert_frame_equal(dk.to_pandas(),
                                  data.set_index("id", drop=False),
                                  check_dtype=False)
    # reset_index restores the column either way
    assert "id" in di.reset_index().columns


def test_index_survives_sort_filter_head(env, data):
    d = ct.DataFrame(data, env=env).set_index("id")
    s = d.sort_values("v", env=env)
    assert s._index == "id"
    exp = data.set_index("id").sort_values("v")
    pd.testing.assert_frame_equal(s.to_pandas(), exp, check_dtype=False)
    f = d[d["w"] > 1.0]
    exp = data.set_index("id")
    exp = exp[exp.w > 1.0]
    pd.testing.assert_frame_equal(f.to_pandas(), exp, check_dtype=False)


def test_merge_ignores_dropped_index(env, data):
    d = ct.DataFrame(data, env=env).set_index("id")
    other = ct.DataFrame(pd.DataFrame({"w": [0.0, 1.0, 2.0],
                                       "z": [9, 8, 7]}), env=env)
    j = d.merge(other, on="w", env=env)
    exp = data.drop(columns="id").merge(pd.DataFrame(
        {"w": [0.0, 1.0, 2.0], "z": [9, 8, 7]}), on="w")
    assert_frames_equal(j.to_pandas().sort_values(["w", "v", "z"]).reset_index(drop=True),
                        exp.sort_values(["w", "v", "z"]).reset_index(drop=True))


def test_isna_notna_dropna_fillna(env, data):
    df = data.copy()
    d = ct.DataFrame(df, env=env)
    pd.testing.assert_frame_equal(d.isna().to_pandas(), df.isna(),
                                  check_dtype=False)
    pd.testing.assert_frame_equal(d.notna().to_pandas(), df.notna(),
                                  check_dtype=False)
    pd.testing.assert_frame_equal(d.dropna().to_pandas().reset_index(drop=True),
                                  df.dropna().reset_index(drop=True),
                                  check_dtype=False)
    pd.testing.assert_frame_equal(
        d.fillna(0.5).to_pandas(), df.fillna(0.5), check_dtype=False)
    # subset + how=all
    pd.testing.assert_frame_equal(
        d.dropna(subset=["v"], how="all").to_pandas().reset_index(drop=True),
        df.dropna(subset=["v"], how="all").reset_index(drop=True),
        check_dtype=False)


def test_frame_arithmetic(env, data):
    df = data.fillna(1.0)
    d = ct.DataFrame(df, env=env)
    pd.testing.assert_frame_equal((d * 2).to_pandas(), df * 2,
                                  check_dtype=False)
    pd.testing.assert_frame_equal((d + 1).to_pandas(), df + 1,
                                  check_dtype=False)
    pd.testing.assert_frame_equal((-d).to_pandas(), -df, check_dtype=False)
    pd.testing.assert_frame_equal((d - d).to_pandas(), df - df,
                                  check_dtype=False)
    pd.testing.assert_frame_equal(d.abs().to_pandas(), df.abs(),
                                  check_dtype=False)


def test_applymap_iterrows_row_scalar(env, data):
    df = data.fillna(0.0)
    d = ct.DataFrame(df, env=env)
    am = d.applymap(lambda x: x * 2)
    pd.testing.assert_frame_equal(am.to_pandas(), df.map(lambda x: x * 2),
                                  check_dtype=False)
    rows = list(d.iterrows())
    assert len(rows) == len(df)
    # Row / Scalar (reference row.hpp / scalar.hpp)
    r = d.row(3)
    assert r["id"] == df.iloc[3]["id"]
    sc = r.scalar("v")
    assert sc == df.iloc[3]["v"] and not sc.is_null
    assert list(r.to_dict()) == list(df.columns)


def test_index_drop_false_survives_loc_iloc_arith(env, data):
    """Regressions from review: drop=False index must survive loc/iloc and
    elementwise ops; drop=True index must survive isna/arithmetic; fillna
    must skip type-incompatible string columns instead of failing."""
    dk = ct.DataFrame(data, env=env).set_index("id", drop=False)
    assert "id" in dk.loc[[2, 3]].columns
    assert "id" in dk.iloc[0:2].columns
    d = ct.DataFrame(data, env=env).set_index("id")
    assert d.isna()._index == "id"
    assert (d * 2)._index == "id"
    assert d.shape == (20, 2) and "id" not in d.dtypes and "id" not in d
    # applymap keeps index labels untouched
    am = d.fillna(0.0).applymap(lambda x: x * 2)
    exp = data.set_index("id").fillna(0.0).map(lambda x: x * 2)
    pd.testing.assert_frame_equal(am.to_pandas(), exp, check_dtype=False)
    # string + numeric fill: string column unchanged, float filled
    sdf = pd.DataFrame({"s": ["a", None, "b"], "v": [1.0, np.nan, 3.0]})
    sd = ct.DataFrame(sdf, env=env).fillna(0.0)
    got = sd.to_pandas()
    assert got["v"].tolist() == [1.0, 0.0, 3.0]
    assert pd.isna(got["s"][1])  # string column left as-is
    # row() hides a dropped index column
    r = d.row(0)
    assert "id" not in r.to_dict()


def test_prefix_suffix_aliases_where_pydict(env1):
    import pandas as pd
    df = pd.DataFrame({"a": [1, 2, 3, 4], "b": [1.0, None, 3.0, 4.0]})
    f = ct.DataFrame(df, env=env1)
    assert f.add_prefix("x_").columns == ["x_a", "x_b"]
    assert f.add_suffix("_y").columns == ["a_y", "b_y"]
    # isnull/notnull aliases
    assert f.isnull().to_pandas()["b"].tolist() == [False, True, False, False]
    assert f.notnull().to_pandas()["a"].all()
    # where with a bool Series: masked slots null (pandas parity)
    cond = f["a"] > 2
    w = f.where(cond).to_pandas()
    exp = df.where(df["a"] > 2)
    assert w["b"].isna().tolist() == exp["b"].isna().tolist()
    # where with other: masked slots filled
    w2 = f.where(cond, 0).to_pandas()
    assert w2["a"].tolist() == [0, 0, 3, 4]
    # to_pydict round trip
    pd2 = f.to_pydict()
    assert pd2["a"] == [1, 2, 3, 4]
    # show/to_string smoke
    assert "a" in f.to_string()
    f.show(2)
