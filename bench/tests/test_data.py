"""The benchmark's copy of the data generator gives exactly the arrays the
program's generator gives."""
import numpy as np
import pytest

import data


@pytest.mark.parametrize("name", ["credit", "covid"])
def test_copy_matches_program(name):
    from repro.dt import load_split
    for mine, theirs in zip(data.load_split(name), load_split(name)):
        assert mine.dtype == theirs.dtype
        np.testing.assert_array_equal(mine, theirs)
