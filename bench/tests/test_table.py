"""The configuration's table: the same for one seed and other for
another, the release's columns with codes inside the schema's, and the
two files the program reads holding the same numbers."""

import numpy as np
import pytest

from bench.harness import table

from conftest import small


def _config(rows=4000):
    return small(rows=rows)[2]


def test_same_seed_same_table_other_seed_other_table():
    c = _config()
    a, b = table.generate(c, 2 ** 31 + 3), table.generate(c, 2 ** 31 + 3)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, table.generate(c, 4))


def test_columns_codes_and_whole_numbers():
    c = _config()
    X = table.generate(c, 11)
    assert X.shape == (4000, 14)
    assert np.array_equal(X, np.round(X)) and not np.isnan(X).any()
    for j, (kind, card) in enumerate(zip(c["schema"]["kinds"],
                                         c["schema"]["cards"])):
        if kind == "categorical":
            assert 0 <= X[:, j].min() and X[:, j].max() < card, j
    assert (X[:, 0] >= 1).all()
    t = c["table"]
    days = X[:, table.COLUMNS.index("date")]
    assert t["first_day"] <= days.min() and days.max() < t["first_day"] + 90


def test_files_read_back_alike(tmp_path):
    c = _config(500)
    table.write(c, 7, str(tmp_path))
    csv_path, npy_path = table.paths(str(tmp_path), c, 0)
    a = np.load(npy_path)
    b = np.loadtxt(csv_path, delimiter=",", skiprows=1)
    assert np.array_equal(a, b)
    assert np.array_equal(a, table.generate(c, 7))


def test_a_schema_that_disagrees_is_refused():
    c = _config()
    schema = dict(c["schema"], cards=list(c["schema"]["cards"]))
    schema["cards"][table.COLUMNS.index("town")] += 1
    with pytest.raises(ValueError):
        table.generate(dict(c, schema=schema), 1)
