"""Golden tests for device sorted-set kernels.

Mirrors the reference's algo/uidlist_test.go semantics: results must equal
numpy's exact sorted-set ops for random sorted inputs, including edge cases
(empty lists, full overlap, disjoint, sentinel-valued UIDs).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from dgraph_tpu.ops import setops


def _mk(rng, n, lo=0, hi=1 << 30):
    return np.unique(rng.integers(lo, hi, size=n, dtype=np.uint64)).astype(
        np.uint32
    )


def _pow2(n):
    return max(8, 1 << (max(1, n) - 1).bit_length())


def _pad(a, size):
    return jnp.asarray(setops.pad_sorted(a, size))


CASES = [
    (0, 0),
    (1, 0),
    (0, 1),
    (10, 10),
    (10, 1000),
    (1000, 10),
    (500, 500),
    (1024, 1024),
]


@pytest.mark.parametrize("na,nb", CASES)
def test_intersect(na, nb):
    rng = np.random.default_rng(na * 1000 + nb)
    a, b = _mk(rng, na), _mk(rng, nb)
    pa, pb = _pow2(len(a)), _pow2(len(b))
    out, n = setops.intersect(_pad(a, pa), len(a), _pad(b, pb), len(b))
    got = np.asarray(out)[: int(n)]
    want = np.intersect1d(a, b, assume_unique=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("na,nb", CASES)
def test_difference(na, nb):
    rng = np.random.default_rng(na * 7 + nb)
    a, b = _mk(rng, na), _mk(rng, nb)
    pa, pb = _pow2(len(a)), _pow2(len(b))
    out, n = setops.difference(_pad(a, pa), len(a), _pad(b, pb), len(b))
    got = np.asarray(out)[: int(n)]
    want = np.setdiff1d(a, b, assume_unique=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("na,nb", CASES)
def test_union(na, nb):
    rng = np.random.default_rng(na * 13 + nb)
    a, b = _mk(rng, na), _mk(rng, nb)
    pa, pb = _pow2(len(a)), _pow2(len(b))
    out, n = setops.union(_pad(a, pa), len(a), _pad(b, pb), len(b))
    got = np.asarray(out)[: int(n)]
    want = np.union1d(a, b)
    np.testing.assert_array_equal(got, want)


def test_sentinel_value_is_valid_uid():
    # 0xFFFFFFFF is a legal UID: validity is judged by length, not sentinel.
    a = np.array([5, 0xFFFFFFFF], dtype=np.uint32)
    b = np.array([0xFFFFFFFF], dtype=np.uint32)
    out, n = setops.intersect(_pad(a, 8), 2, _pad(b, 8), 1)
    np.testing.assert_array_equal(np.asarray(out)[: int(n)], [0xFFFFFFFF])
    out, n = setops.union(_pad(a, 8), 2, _pad(b, 8), 1)
    np.testing.assert_array_equal(np.asarray(out)[: int(n)], [5, 0xFFFFFFFF])
    out, n = setops.difference(_pad(a, 8), 2, _pad(b, 8), 1)
    np.testing.assert_array_equal(np.asarray(out)[: int(n)], [5])


def test_merge_sorted_kway():
    rng = np.random.default_rng(0)
    lists = [_mk(rng, n) for n in (50, 200, 0, 130, 1)]
    pad = 256
    L = np.stack([setops.pad_sorted(x, pad) for x in lists])
    lens = np.array([len(x) for x in lists], np.int32)
    out, n = setops.merge_sorted(jnp.asarray(L), jnp.asarray(lens))
    want = np.unique(np.concatenate(lists))
    np.testing.assert_array_equal(np.asarray(out)[: int(n)], want)


def test_intersect_many():
    rng = np.random.default_rng(1)
    base = _mk(rng, 400, hi=1 << 12)
    lists = [base]
    for _ in range(3):
        extra = _mk(rng, 300, hi=1 << 12)
        lists.append(np.union1d(base[::2], extra))
    pad = 1024
    L = np.stack([setops.pad_sorted(x, pad) for x in lists])
    lens = np.array([len(x) for x in lists], np.int32)
    out, n = setops.intersect_many(jnp.asarray(L), jnp.asarray(lens))
    want = lists[0]
    for x in lists[1:]:
        want = np.intersect1d(want, x, assume_unique=True)
    np.testing.assert_array_equal(np.asarray(out)[: int(n)], want)


def test_batched_vmap_matches_scalar():
    rng = np.random.default_rng(2)
    import jax

    pairs = [(_mk(rng, 100), _mk(rng, 300)) for _ in range(6)]
    pa = pb = 512
    A = np.stack([setops.pad_sorted(a, pa) for a, _ in pairs])
    B = np.stack([setops.pad_sorted(b, pb) for _, b in pairs])
    LA = np.array([len(a) for a, _ in pairs], np.int32)
    LB = np.array([len(b) for _, b in pairs], np.int32)
    out, n = jax.vmap(setops.intersect)(
        jnp.asarray(A), jnp.asarray(LA), jnp.asarray(B), jnp.asarray(LB)
    )
    for i, (a, b) in enumerate(pairs):
        want = np.intersect1d(a, b, assume_unique=True)
        np.testing.assert_array_equal(np.asarray(out[i])[: int(n[i])], want)
