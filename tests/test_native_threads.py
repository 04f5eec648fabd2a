"""Threaded stress corpus for the -pthread native kernels.

The three kernels that spin std::thread fan-outs internally —
vec_qi8_topk_lists (IVF probe batches), vec_qi8_quantize (row
quantizer), batch_apply (columnar group-commit apply) — are here
hammered from many *Python* threads at once, each call itself
multi-threaded, over shared read-only inputs. Two jobs:

  1. tier-1 (plain build): caller-concurrency determinism — every
     concurrent call must return bytes identical to the solo call
     (a race on shared input handling or a hidden global shows up as
     a divergent result);
  2. the TSan target corpus: `tools/check.sh --san-matrix` re-runs
     this module with DGRAPH_TPU_NATIVE_SAN=tsan, where any data race
     inside the fan-outs (or between concurrent callers) aborts the
     interpreter. TSan is the only tool that can see those races —
     the GIL is released for the entire native call.

batch_apply inputs are captured from a real seeded group-commit
workload (capture-and-replay), so the concurrent batches are exactly
the shapes production emits, not synthetic columns.
"""

import threading

import numpy as np
import pytest

from dgraph_tpu import native
from dgraph_tpu.models import vector
from dgraph_tpu.x import config

requires_native = pytest.mark.skipif(
    not native.NATIVE_AVAILABLE, reason="native codec library not built"
)

N_THREADS = 6
ITERS = 4


def _hammer(fn):
    """Run fn(thread_idx, iter_idx) from N_THREADS threads x ITERS
    iterations, barrier-aligned for maximal overlap; re-raise the
    first failure."""
    barrier = threading.Barrier(N_THREADS)
    errors = []

    def worker(t):
        try:
            barrier.wait(timeout=30)
            for i in range(ITERS):
                fn(t, i)
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(N_THREADS)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not any(th.is_alive() for th in threads), "stress worker hung"
    if errors:
        raise errors[0]


@requires_native
def test_topk_lists_concurrent_callers():
    rng = np.random.default_rng(31)
    n, d, nq, k = 2500, 32, 8, 8
    V = rng.standard_normal((n, d)).astype(np.float32)
    codes, scales, offsets, csums = vector._quantize(V)
    sqn = (V * V).sum(axis=1, dtype=np.float32)
    valid = np.ones((n,), np.uint8)
    valid[rng.choice(n, 250, replace=False)] = 0
    Q = rng.standard_normal((nq, d)).astype(np.float32)
    cand = [
        np.sort(
            rng.choice(n, int(rng.integers(1, 900)), replace=False)
        ).astype(np.int32)
        for _ in range(nq)
    ]
    cand[3] = np.zeros((0,), np.int32)  # empty slice
    cand[5] = cand[1]                    # aliased slice
    lens = np.array([c.size for c in cand], np.int64)
    ends = np.cumsum(lens)
    begs = ends - lens
    cat = np.concatenate(cand)
    qc, qs, qo, qcs, qstat = vector._quantize_queries(Q, "euclidean")
    mid = vector._METRIC_ID["euclidean"]

    def call():
        return native.vec_qi8_topk_lists(
            codes, scales, offsets, csums, sqn, valid,
            cat, begs, ends, qc, qs, qo, qcs, qstat, mid, k,
            nthreads=3,
        )

    want_idx, want_dist, want_scanned = call()

    def body(_t, _i):
        got_idx, got_dist, got_scanned = call()
        np.testing.assert_array_equal(got_idx, want_idx)
        np.testing.assert_array_equal(got_dist, want_dist)
        assert got_scanned == want_scanned

    _hammer(body)


@requires_native
def test_quantize_concurrent_callers():
    rng = np.random.default_rng(32)
    n, d = 900, 67  # odd dim: SIMD tail under thread splits
    V = rng.standard_normal((n, d)).astype(np.float32)
    V *= (10.0 ** rng.uniform(-5, 5, size=n)).astype(np.float32)[:, None]
    V[3] = 0.0

    def call():
        return native.vec_qi8_quantize(V, nthreads=2)

    want = call()
    assert want is not None

    def body(_t, _i):
        got = native.vec_qi8_quantize(V, nthreads=((_t % 3) + 1))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    _hammer(body)


def _capture_batches():
    """Run a small seeded mutation workload with the columnar path
    forced on, capturing every batch_apply call's input columns (deep
    copies: the write sets recycle their buffers)."""
    from array import array

    from dgraph_tpu.api.server import Server

    captured = []
    real = native.batch_apply

    def spy(m_offs, shapes, entities, pred_ids, objects, vtypes, voffs,
            vblob, pp_blob, pp_offs, pflags, pidents):
        captured.append((
            m_offs[:], bytearray(shapes), entities[:], pred_ids[:],
            objects[:], bytearray(vtypes), voffs[:], bytearray(vblob),
            bytes(pp_blob), pp_offs[:], bytes(pflags), bytes(pidents),
        ))
        return real(m_offs, shapes, entities, pred_ids, objects, vtypes,
                    voffs, vblob, pp_blob, pp_offs, pflags, pidents)

    config.set_env("BATCH_APPLY", 1)
    native.batch_apply = spy
    try:
        rng = np.random.default_rng(33)
        s = Server()
        s.alter(
            "name: string @index(exact) .\n"
            "bio: string @index(term) .\n"
            "age: int @index(int) .\n"
            "knows: [uid] @reverse ."
        )
        auto = 0
        for _ in range(6):
            t = s.new_txn()
            objs = []
            for _ in range(int(rng.integers(2, 6))):
                auto += 1
                objs.append({
                    "uid": f"_:n{auto}",
                    "name": f"user{int(rng.integers(0, 30))}",
                    "bio": f"likes topic{int(rng.integers(0, 9))} daily",
                    "age": int(rng.integers(0, 99)),
                    "knows": [{"uid": hex(int(rng.integers(1, 16)))}],
                })
            t.mutate_json(set_obj=objs, commit_now=True)
    finally:
        native.batch_apply = real
        config.unset_env("BATCH_APPLY")
    assert isinstance(captured[0][0], array)  # shape sanity
    return captured


@requires_native
def test_batch_apply_concurrent_batches(monkeypatch):
    # APPLY_PROCS=auto sends the batches to worker processes on any
    # multi-core box, where the in-process spy sees nothing
    monkeypatch.setenv("DGRAPH_TPU_APPLY_PROCS", "0")
    batches = _capture_batches()
    assert batches, "columnar path never reached the kernel"
    want = [native.batch_apply(*b) for b in batches]

    def norm(res):
        n_pairs, keys, koffs, recs, roffs, member, pred, kinds, counts = res
        return (
            n_pairs, bytes(keys), list(koffs), bytes(recs), list(roffs),
            list(member), list(pred), list(kinds), list(counts),
        )

    want = [norm(w) for w in want]

    def body(t, i):
        b = batches[(t + i) % len(batches)]
        assert norm(native.batch_apply(*b)) == want[(t + i) % len(batches)]

    _hammer(body)
