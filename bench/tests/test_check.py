"""The comparison's helpers and the datasets' draws, on fixed data."""
import numpy as np
import pandas as pd
import pytest

from bench.check import Tally, top_rows
from bench.draw import categorical

WANT = pd.DataFrame({"k": [5, 9, 9, 7, 9, 1], "v": [0, 1, 2, 3, 4, 5]})


@pytest.mark.parametrize("got, bad", [
    ({"k": [9, 9, 9, 7], "v": [1, 2, 4, 3]}, 0),
    ({"k": [9, 9, 9, 7], "v": [4, 1, 2, 3]}, 0),     # ties in any order
    ({"k": [9, 9, 9, 7], "v": [1, 1, 2, 3]}, 1),     # one row twice
    ({"k": [9, 9, 9, 7], "v": [1, 2, 4, 0]}, 1),     # a row of another key
    ({"k": [9, 9, 7, 5], "v": [1, 2, 3, 0]}, 3),     # a top row left out
])
def test_top_rows_takes_ties_in_any_order(got, bad):
    tally = Tally()
    top_rows(tally, "t", {c: np.asarray(v) for c, v in got.items()}, WANT,
             "k", 4)
    assert tally.mismatches == bad


def test_top_rows_columns_must_match():
    tally = Tally()
    top_rows(tally, "t", {"v": np.arange(4), "k": np.arange(4)}, WANT, "k", 4)
    assert tally.mismatches == 1


def test_categorical_draws_each_value_its_share():
    rng = np.random.default_rng(2**31 + 17)
    got = categorical(rng, 400_000, np.array([10, 20, 30]), [1, 2, 5])
    share = np.array([(got == v).mean() for v in (10, 20, 30)])
    assert set(np.unique(got)) == {10, 20, 30}
    np.testing.assert_allclose(share, [1 / 8, 2 / 8, 5 / 8], atol=5e-3)
    again = categorical(np.random.default_rng(2**31 + 17), 400_000,
                        np.array([10, 20, 30]), [1, 2, 5])
    assert np.array_equal(got, again)
