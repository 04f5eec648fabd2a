"""`query/ragged.py`'s `apply_mask`: a level's kept ids and their new
offsets, against the running count over every id it replaced."""

import numpy as np
import pytest

from dgraph_tpu.query import ragged


def _by_running_count(flat, offs, mask):
    cum = np.zeros((flat.size + 1,), np.int64)
    np.cumsum(mask, out=cum[1:])
    return flat[mask], cum[offs]


def _level(lens, seed=0):
    rng = np.random.default_rng(seed)
    offs = np.zeros((len(lens) + 1,), np.int64)
    np.cumsum(lens, out=offs[1:])
    flat = np.sort(rng.integers(1, 1 << 40, int(offs[-1]), dtype=np.uint64))
    return flat, offs


def _case(name):
    rng = np.random.default_rng(len(name))
    if name == "empty_level":
        flat, offs = ragged.EMPTY, np.zeros((1,), np.int64)
    elif name == "empty_rows":
        flat, offs = _level([0, 0, 0])
    elif name == "one_row":
        flat, offs = _level([57])
    elif name == "ic1_third_level":
        flat, offs = _level(rng.multinomial(150_000, [1 / 1689] * 1689))
    else:
        flat, offs = _level([0, 12, 0, 30, 1, 0])
    if name == "all_kept":
        mask = np.ones(flat.size, bool)
    elif name == "none_kept":
        mask = np.zeros(flat.size, bool)
    else:
        mask = rng.random(flat.size) < rng.random()
    return flat, offs, mask


@pytest.mark.parametrize("name", [
    "empty_level", "empty_rows", "all_kept", "none_kept", "one_row",
    "ragged_rows", "ic1_third_level"])
def test_apply_mask_is_the_running_count(name):
    flat, offs, mask = _case(name)
    got, got_offs = ragged.apply_mask(flat, offs, mask)
    want, want_offs = _by_running_count(flat, offs, mask)
    assert got.dtype == want.dtype and got_offs.dtype == want_offs.dtype
    assert np.array_equal(got, want) and np.array_equal(got_offs, want_offs)
