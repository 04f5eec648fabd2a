"""`vec.insert16`'s read check judges each answer by the probe that
served it (`chipbench/queries/similar_to_live.py` `probed`): the float32
distances the probe returned against float64 at the state the answer is
judged at. Sound distances read a few ulps; distances one precision
step below read several times the limit; a row returned at another
value, or not live, makes the answer wrong at that state and not at
the state the probe ran at."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench.data import mog, mog_live
from chipbench.queries import similar_to_live as kind

D, N, K = 64, 2048, 10


class Log:
    """A `mog_live.ProbeLog` with its table given."""

    def __init__(self):
        self.table = {}

    def of(self, q):
        return self.table.get(np.asarray(q, np.float32).tobytes())


def probe(V, rows, q, precision: str = "highest"):
    """float32 distances of rows of V to q as the chip's probe computes
    them; `high` keeps three bfloat16 products of each term (the rounding
    of `precision="high"` on a TPU)."""
    import ml_dtypes

    X = V[rows]
    if precision == "high":
        def split(x):
            hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
            return hi, (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
        (xh, xl), (qh, ql) = split(X), split(q)
        dot = xh @ qh + xh @ ql + xl @ qh
    else:
        dot = X @ q
    return (X * X).sum(axis=1) - np.float32(2.0) * dot + (q * q).sum()


@pytest.fixture
def case():
    rng = np.random.default_rng(5)
    centers = rng.standard_normal((8, D)).astype(np.float32) * 4
    V = (centers[rng.integers(0, 8, N)]
         + rng.standard_normal((N, D))).astype(np.float32)
    model = mog_live.Model(mog.Model(V), Log())
    q = (V[7] + 0.05 * rng.standard_normal(D)).astype(np.float32)
    want = model.topk([q], K)[0][0]
    rows = (want - mog.UID_BASE).astype(np.int64)
    return model, V, q, want, rows


def numbers(model, q, answer):
    got = kind.check(model, {"k": K}, [(q, None, False)],
                     [np.sort(np.asarray(answer, np.uint64))])
    return {name: vals[0] for name, vals in got.items()}


@pytest.mark.parametrize("precision,over", [("highest", False),
                                            ("high", True)])
def test_probed_distances_read_in_ulps_against_float64(case, precision,
                                                       over):
    model, V, q, want, rows = case
    model.probes.table[q.tobytes()] = (want.astype(np.int64),
                                       probe(V, rows, q, precision))
    got = numbers(model, q, want)
    assert got["wrong_answers"] == 0 and got["probes_compared"] == 1
    assert (got["probe_dist_error_ulps"] > 4.0) == over
    assert got["probe_dist_error_ulps"] < kind.STALE_ULPS


def test_an_answer_with_no_probe_logged_compares_none(case):
    model, V, q, want, rows = case
    got = numbers(model, q, want)
    assert got["probes_compared"] == 0 and got["probe_dist_error_ulps"] == 0
    assert got["wrong_answers"] == 0 and got["recall_at_k"] == 1.0


@pytest.mark.parametrize("write", ["reembed", "delete"])
def test_a_row_probed_at_another_state_is_wrong_here(case, write):
    """The probe ran before a write changed a row it returned past the
    answer's k: the answer reads right before the write and wrong after
    it, though every uid it names is live at its value."""
    model, V, q, want, rows = case
    pool = model.topk([q], K + 1)[0][0]
    model.probes.table[q.tobytes()] = (
        pool.astype(np.int64),
        probe(V, (pool - mog.UID_BASE).astype(np.int64), q))
    moved = int(pool[-1])
    if write == "reembed":
        model.put(moved, V[0] + 50.0)
    else:
        model.kill(moved)
    assert numbers(model, q, want)["wrong_answers"] == 1
    before = mog_live.Model(model.base, model.probes)
    assert numbers(before, q, want)["wrong_answers"] == 0
