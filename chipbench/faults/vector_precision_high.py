"""Fault `vector_precision_high`: the vector index scores one precision
step below the float32-at-`highest` that `vec-1m-768-live` states, as
`precision="high"` defines it: three bfloat16 products of each term
(hi.hi + hi.lo + lo.hi, each operand split into a bfloat16 head and a
bfloat16 tail, accumulated in float32), in the euclidean distances of the
IVF probe and of the brute tiers, and `high` in the assignment of appended
rows to their cells. The probe's product of slab rows and ONE query is
a matrix-vector product, which XLA on a TPU computes as a float32
multiply and reduce whatever `precision` asks, so the split is written
out; the parts are rounded to bfloat16 on the float32's bits (no
compiler folds that away), on any backend. It breaks
`probe_dist_error_ulps`, which compares each answer's probed distances
with float64 (`chipbench/queries/similar_to_live.py`); an answer's
uids rarely move, for bfloat16x3 errors are far below the gaps between
neighbours. Planted before any program is traced."""

from __future__ import annotations


def plant() -> None:
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.models import vector

    def head(x):
        """x rounded to the nearest bfloat16, ties to even."""
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32)

    def split(x):
        hi = head(x)
        return hi, head(x - hi)

    def matmul3(a, b):
        (ah, al), (bh, bl) = split(a), split(b)
        mm = lambda x, y: jnp.matmul(x, y, precision="highest")  # noqa: E731
        return mm(ah, bh) + mm(ah, bl) + mm(al, bh)

    one, batch = vector._distances, vector._distances_batch

    def distances(V, sqnorm, q, metric):
        if metric != "euclidean":
            return one(V, sqnorm, q, metric)
        return sqnorm - 2.0 * matmul3(V, q) + (q * q).sum()

    def distances_batch(V, sqnorm, Q, metric):
        if metric != "euclidean":
            return batch(V, sqnorm, Q, metric)
        return (sqnorm[None, :] - 2.0 * matmul3(Q, V.T)
                + (Q * Q).sum(axis=1)[:, None])

    vector._PRECISION = "high"
    vector._distances = distances
    vector._distances_batch = distances_batch
