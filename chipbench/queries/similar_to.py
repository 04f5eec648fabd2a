"""Query kind `similar_to`: one `similar_to(emb, k, <vector>)` with a
query vector drawn from the mixture that no other request of the run
repeats (every draw is fresh noise around a drawn centre)."""

from __future__ import annotations

import numpy as np

from chipbench.data import mog

FAULTS = ("same_cluster", "next_nearest", "next_nearest_third")
BAD = 1e9  # an answer of the wrong length or naming a uid outside the corpus


def request(catalog: dict, params: dict, rng):
    """(key, DQL text): the key is the query vector."""
    centers = catalog["centers"]
    c = int(rng.integers(0, len(centers)))
    q = (centers[c] + rng.standard_normal(centers.shape[1])).astype(np.float32)
    text = '{ res(func: similar_to(%s, %d, "%s")) { uid } }' % (
        mog.PRED, params["k"], [float(x) for x in q])
    return q, text


def parse(body: dict) -> np.ndarray:
    if "errors" in body:
        raise ValueError(str(body["errors"])[:200])
    return np.array([int(r["uid"], 16) for r in body["data"]["res"]],
                    np.uint64)


EPS32 = float(np.finfo(np.float32).eps)


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    """Numbers against exact float64 neighbours. Per answer (the
    configuration's `checks` say how each is aggregated and limited; the
    index is approximate, so a lone answer may miss a neighbour for the
    next nearest row, and no number is a single answer's worst):
    recall_at_k       share of the exact k the answer names;
    dist_excess       how much farther the answer's i-th nearest is than
                      the exact i-th nearest, worst rank, as a share;
    inexact_answers   1 where the answer's set is not the exact set.
    The response carries uids only, in uid order, so the arithmetic is
    read from the probe itself. Per tapped probe call (`captured`:
    query, rows, the float32 distances the compiled program returned):
    probe_dist_error_ulps   the widest gap between a returned distance
                      and the float64 distance of that row, in float32
                      ulps of the largest term (|v|^2 + |q|^2);
    probe_dist_rms_ulps     the root mean square of those gaps."""
    k = params["k"]
    want = model.topk(np.stack(keys), k)
    out = {"recall_at_k": [], "dist_excess": [], "inexact_answers": [],
           "probe_dist_error_ulps": [], "probe_dist_rms_ulps": [],
           "probes_compared": [1.0] * len(captured or [])}
    for q, got, (want_uids, want_d) in zip(keys, answers, want):
        got_d = model.dists(got, q)
        if len(got) != k or len(set(got.tolist())) != k or not np.all(
                np.isfinite(got_d)):
            out["recall_at_k"].append(0.0)
            out["dist_excess"].append(BAD)
            out["inexact_answers"].append(1.0)
            continue
        out["recall_at_k"].append(
            len(set(got.tolist()) & set(want_uids.tolist())) / k)
        out["dist_excess"].append(
            float(np.max(np.sort(got_d) / want_d - 1.0)))
        out["inexact_answers"].append(
            float(set(got.tolist()) != set(want_uids.tolist())))
    for q, rows, dd in captured or []:
        ok = (rows >= 0) & (rows < len(model.V)) & np.isfinite(dd)
        if not ok.any():
            out["probe_dist_error_ulps"].append(BAD)
            out["probe_dist_rms_ulps"].append(BAD)
            continue
        d64 = model.d64(rows[ok], q)
        scale = EPS32 * (model.sq[rows[ok]] + float(q @ q))
        err = (dd[ok].astype(np.float64) - d64) / scale
        out["probe_dist_error_ulps"].append(float(np.max(np.abs(err))))
        out["probe_dist_rms_ulps"].append(float(np.sqrt(np.mean(err ** 2))))
    return out


def faults(model, params: dict, keys: list, answers: list, seed: int) -> dict:
    """{name: answers} with a wrong neighbour planted in the answers
    the program gave, as near a miss as a fault can make (their readings
    set the upper ends of `inexact_answers` and `dist_excess`):
    same_cluster   in EVERY answer the nearest neighbour gives way to a
                   row drawn from the same gaussian of the mixture;
    next_nearest   in EVERY answer the farthest gives way to the exact
                   (k+1)-th: what the approximate index itself does
                   where its probe misses a row, but then in a lone
                   answer of a run;
    next_nearest_third   the same in every third answer only."""
    k = params["k"]
    nxt = model.topk(np.stack(keys), k + 1)
    out = {"same_cluster": [], "next_nearest": [], "next_nearest_third": []}
    for i, (q, got, (uids, _)) in enumerate(zip(keys, answers, nxt)):
        d = model.dists(got, q)
        rng = np.random.default_rng([seed, 79, i])
        mates = np.flatnonzero(
            model.labels == model.labels[int(uids[0]) - mog.UID_BASE])
        mates = np.setdiff1d(mates.astype(np.uint64) + mog.UID_BASE, got)
        swapped = got.copy()
        swapped[int(np.argmin(d))] = rng.choice(mates)
        out["same_cluster"].append(np.sort(swapped))
        swapped = got.copy()
        swapped[int(np.argmax(d))] = next(
            u for u in uids if u not in set(got.tolist()))
        out["next_nearest"].append(np.sort(swapped))
        out["next_nearest_third"].append(
            np.sort(swapped) if i % 3 == 2 else got)
    return out


def control(model, params: dict, keys: list):
    """The reference in the program's place, one precision step below
    the float32-at-`highest` the configuration states: `high`, three
    bf16 passes. The operands are split on the host (x = hi + lo, both
    exact in bfloat16) and the three products hi.hi + hi.lo + lo.hi are
    accumulated in float32, so it is the same arithmetic on any backend
    (split inside the jitted program, XLA on the TPU folds the
    round-trip away). Brute force over the whole corpus in row blocks.
    Returns (answers, captured) in the shapes `check` takes."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    k = params["k"]

    def split(x):
        hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
        return jnp.asarray(hi), jnp.asarray(lo)

    @jax.jit
    def block_topk(vh, vl, sq, qh, ql, qsq):
        mm = lambda a, b: jnp.matmul(a, b.T, precision="highest")  # noqa: E731
        dot = mm(qh, vh) + mm(qh, vl) + mm(ql, vh)
        neg, idx = jax.lax.top_k(-(sq[None, :] - 2.0 * dot + qsq[:, None]), k)
        return -neg, idx

    Q = np.stack(keys)
    qh, ql = split(Q)
    qsq = jnp.asarray(np.einsum("ij,ij->i", Q, Q))
    n, rows = len(model.V), 131072
    best_d = np.zeros((len(keys), 0), np.float32)
    best_i = np.zeros((len(keys), 0), np.int64)
    for off in range(0, n, rows):
        V = model.V[off:off + rows]
        if len(V) < k:
            V = np.concatenate([V, np.full((k - len(V), V.shape[1]), 1e6,
                                           np.float32)])
        d, i = block_topk(*split(V), jnp.asarray(
            np.einsum("ij,ij->i", V, V)), qh, ql, qsq)
        best_d = np.concatenate([best_d, np.asarray(d)], axis=1)
        best_i = np.concatenate([best_i, np.asarray(i) + off], axis=1)
    order = np.argsort(best_d, axis=1, kind="stable")[:, :k]
    rows_k = np.take_along_axis(best_i, order, axis=1)
    d_k = np.take_along_axis(best_d, order, axis=1)
    answers = [np.sort(r.astype(np.uint64) + mog.UID_BASE) for r in rows_k]
    return answers, list(zip(Q, rows_k, d_k))
