"""Query kind `similar_to_live`: one `similar_to(emb, k, <vector>)` over a
corpus that takes writes. After its client's write it queries the
vector written (or deleted) plus noise of `vec_writes.ANCHOR_NOISE` a
coordinate, the write's row being its anchor; else a query drawn as
`similar_to` draws it (`chipbench/queries/vec_writes.py`). Judged at a
state of the commit history (`chipbench/history.py`) against the exact
top-k of the rows live there, each at its value there
(`chipbench/data/mog_live.Model`), and by the probe that served it:
its float32 distances (`mog_live.ProbeLog`) against float64 at the same
state; its control is that exact answer one acknowledged write
behind."""

from __future__ import annotations

import numpy as np

from chipbench.data import mog
from chipbench.queries import similar_to
from chipbench.queries import vec_writes as w
from chipbench.queries.similar_to import parse  # noqa: F401

BAD = similar_to.BAD
EPS32 = similar_to.EPS32
# a probed distance this far off is no rounding: the row was at another
# value, or not live, where the probe ran
STALE_ULPS = 1024.0


def request(catalog: dict, params: dict, rng):
    """(key, DQL text): the key is (query vector, anchor uid or None,
    whether the anchor's write deleted it)."""
    anchor = w.take(catalog, rng)
    if anchor is None:
        q, text = similar_to.request(catalog, params, rng)
        return (q, None, False), text
    uid, vec, deleted = anchor
    q = (vec + w.ANCHOR_NOISE * rng.standard_normal(vec.shape[0])).astype(
        np.float32)
    text = '{ res(func: similar_to(%s, %d, "%s")) { uid } }' % (
        mog.PRED, params["k"], [float(x) for x in q])
    return (q, uid, deleted), text


def probed(model, q: np.ndarray):
    """(ulps, stale) of the probe that served query `q`, judged here:
    the widest gap between a float32 distance it returned and the
    float64 distance of that row's uid at its value here, in float32
    ulps of the largest term (|v|^2 + |q|^2), as `similar_to`'s
    `probe_dist_error_ulps`; and whether a row it returned is not live
    here, or is off by more than STALE_ULPS (at another value here).
    None where no probe of `q` was logged."""
    got = model.probes.of(q) if model.probes is not None else None
    if got is None:
        return None
    uids, dd = got
    V, ok = model.values(uids)
    q64 = q.astype(np.float64)
    diff = V - q64
    err = np.abs(dd.astype(np.float64) - np.einsum("ij,ij->i", diff, diff))
    err = (err / (EPS32 * (np.einsum("ij,ij->i", V, V) + q64 @ q64)))[ok]
    worst = float(err.max()) if err.size else 0.0
    return worst, bool(not ok.all() or worst > STALE_ULPS)


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    """One number an answer, at the model's state (the configuration's
    `checks` aggregate them):
    wrong_answers     1 where the answer has the wrong length or a
                      repeated uid, names a uid not live here, or omits
                      its anchor where the anchor is the exact nearest;
                      or where the probe that served it returned a row
                      not live here or at another value (`probed`);
    recall_at_k, dist_excess, inexact_answers   as `similar_to`'s,
                      against the exact top-k of the live rows here;
    probe_dist_error_ulps   `probed`'s ulps (0 where no probe was
                      logged); probes_compared 1 where one was;
    answers_compared  1 an answer; session_reads 1 where it had an
                      anchor."""
    k = params["k"]
    want = model.topk([key[0] for key in keys], k)
    out = {"wrong_answers": [], "recall_at_k": [], "dist_excess": [],
           "inexact_answers": [], "answers_compared": [],
           "session_reads": [], "probe_dist_error_ulps": [],
           "probes_compared": []}
    for (q, anchor, _), got, (want_u, want_d) in zip(keys, answers, want):
        got = np.asarray(got).astype(np.int64)
        got_d = model.dists(got, q)
        missed = anchor is not None and anchor == want_u[0] and (
            anchor not in got.tolist())
        probe = probed(model, q)
        wrong = (len(got) != k or len(set(got.tolist())) != k
                 or not np.all(np.isfinite(got_d)) or missed
                 or (probe is not None and probe[1]))
        out["wrong_answers"].append(float(wrong))
        out["answers_compared"].append(1.0)
        out["session_reads"].append(float(anchor is not None))
        out["probe_dist_error_ulps"].append(probe[0] if probe else 0.0)
        out["probes_compared"].append(float(probe is not None))
        if len(got) != k or len(set(got.tolist())) != k or not np.all(
                np.isfinite(got_d)):
            out["recall_at_k"].append(0.0)
            out["dist_excess"].append(BAD)
            out["inexact_answers"].append(1.0)
            continue
        same = set(got.tolist()) & set(want_u.tolist())
        out["recall_at_k"].append(len(same) / k)
        out["dist_excess"].append(float(np.max(np.sort(got_d) / want_d - 1.0)))
        out["inexact_answers"].append(float(len(same) != k))
    return out


def control(model, params: dict, keys: list):
    """The exact answer at the model's state, in uid order as the
    program answers; the harness takes it one acknowledged write
    behind."""
    got = model.topk([key[0] for key in keys], params["k"])
    return [np.sort(u.astype(np.uint64)) for u, _ in got], None
