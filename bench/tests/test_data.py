"""The benchmark's copies of the program's synthetic generators draw what
the originals draw: an edit to either side shows here."""
import jax
import numpy as np
import pytest

from benchkit import data


@pytest.mark.parametrize("seed", [0, 3100000202])
def test_token_batch_matches_original(seed):
    from repro.data import synthetic
    key = data.seed_key(seed)
    ours = data.token_batch(key, 3, 64, 50304)
    theirs = synthetic.token_batch(key, 3, 64, 50304)
    for name in ("tokens", "labels"):
        np.testing.assert_array_equal(np.asarray(ours[name]),
                                      np.asarray(theirs[name]))


def test_digit_store_matches_original():
    from repro.data import synthetic
    key = data.seed_key(3100000202)
    ours = data.digit_dataset(key, 256)
    theirs = synthetic.digit_dataset(key, 256)
    for name in ("images", "labels"):
        np.testing.assert_array_equal(np.asarray(ours[name]),
                                      np.asarray(theirs[name]))
    k_part = jax.random.fold_in(key, 1)
    for a, b in zip(data.partition_iid(k_part, 256, 7),
                    synthetic.partition_iid(k_part, 256, 7)):
        np.testing.assert_array_equal(a, b)
