"""Multi-device sharding: the distributed data plane.

The reference distributes work via predicate sharding + gRPC fan-out
(/root/reference/worker/groups.go tablet routing, conn/ transport). The
TPU-native equivalent (SURVEY.md §2.3): the *control* plane (membership,
tablet map, txn oracle) stays host-side, while the *data* plane — giant
posting lists and vector matrices — shards across TPU devices over a
jax.sharding.Mesh, with XLA collectives (psum / all_gather) riding ICI.

Axes:
  "data"  — row sharding: UID-pack tiles of one giant list ("sequence
            parallel" analog of the reference's multi-part list splits,
            posting/list.go:44 maxListSize), vector DB rows, k-means
            training batch.

All functions take an explicit Mesh and work on any device count,
including the virtual 8-device CPU mesh used by tests and the driver's
dryrun (xla_force_host_platform_device_count).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dgraph_tpu.ops import setops


def make_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


# ---------------------------------------------------------------------------
# Distributed membership/intersect: a sharded by rows, b replicated.
# The giant-list analog of multi-part posting lists: each device holds a
# contiguous tile of `a`, checks membership against (replicated) `b`.
# ---------------------------------------------------------------------------


def sharded_membership(mesh: Mesh, a: jnp.ndarray, la, b: jnp.ndarray, lb):
    """mask over row-sharded `a` (padded multiple of n_devices)."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("data"), P(), P(), P()),
        out_specs=P("data"),
    )
    def _member(a_tile, la_all, b_all, lb_all):
        n = a_tile.shape[0]
        didx = jax.lax.axis_index("data")
        start = didx * n
        # local validity window: index < la - start
        local_len = jnp.clip(la_all - start, 0, n)
        return setops.membership(a_tile, local_len, b_all, lb_all)

    return _member(a, jnp.asarray(la, jnp.int32), b, jnp.asarray(lb, jnp.int32))


def sharded_rows_membership(mesh: Mesh, A, LA, b, lb):
    """Membership of a replicated row batch in a ROW-SHARDED big list.

    A: (n, pa) replicated padded sorted u32 rows; LA: (n,) lengths;
    b: row-sharded padded sorted u32 (multiple of mesh size); lb: total
    valid length. Returns (n, pa) bool mask — element of A present in b.

    This is the query-side face of multi-part posting lists: each device
    holds a tile of the giant list (one or more parts), checks the whole
    level's rows against its tile, and the masks OR-reduce over ICI
    (psum>0). Ref worker/task.go fan-out replaced by one collective."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P("data"), P()),
        out_specs=P(),
    )
    def _member(A_all, LA_all, b_tile, lb_all):
        tile_n = b_tile.shape[0]
        start = jax.lax.axis_index("data") * tile_n
        local_len = jnp.clip(lb_all - start, 0, tile_n)
        m = jax.vmap(setops.membership, in_axes=(0, 0, None, None))(
            A_all, LA_all, b_tile, local_len
        )
        return jax.lax.psum(m.astype(jnp.int32), "data") > 0

    return _member(
        A, jnp.asarray(LA, jnp.int32), b, jnp.asarray(lb, jnp.int32)
    )


def sharded_intersect_count(mesh: Mesh, a, la, b, lb):
    """Total intersection size of a row-sharded list vs replicated list
    (psum over the mesh)."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("data"), P(), P(), P()),
        out_specs=P(),
    )
    def _count(a_tile, la_all, b_all, lb_all):
        n = a_tile.shape[0]
        start = jax.lax.axis_index("data") * n
        local_len = jnp.clip(la_all - start, 0, n)
        m = setops.membership(a_tile, local_len, b_all, lb_all)
        return jax.lax.psum(jnp.sum(m, dtype=jnp.int32), "data")

    return _count(a, jnp.asarray(la, jnp.int32), b, jnp.asarray(lb, jnp.int32))


# ---------------------------------------------------------------------------
# Distributed vector search: V row-sharded, query replicated.
# Local top-k per shard -> all_gather -> global top-k. ("TP" over DB rows.)
# ---------------------------------------------------------------------------


def sharded_topk(mesh: Mesh, V: jnp.ndarray, valid: jnp.ndarray, q: jnp.ndarray, k: int):
    """Returns (global top-k squared-euclidean distances, global row ids)."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P()),
        out_specs=(P(), P()),
        # outputs are replicated post-all_gather; vma tracking can't see it
        check_vma=False,
    )
    def _topk(V_tile, valid_tile, q_all):
        rows = V_tile.shape[0]
        d2 = ((V_tile - q_all[None, :]) ** 2).sum(axis=1)
        d2 = jnp.where(valid_tile, d2, jnp.inf)
        kk = min(k, rows)
        neg, idx = jax.lax.top_k(-d2, kk)
        base = jax.lax.axis_index("data") * rows
        gidx = idx + base
        # gather every shard's candidates, then reduce to global top-k
        all_neg = jax.lax.all_gather(neg, "data")
        all_idx = jax.lax.all_gather(gidx, "data")
        flat_neg = all_neg.reshape(-1)
        flat_idx = all_idx.reshape(-1)
        gneg, sel = jax.lax.top_k(flat_neg, k)
        return -gneg, jnp.take(flat_idx, sel)

    return _topk(V, valid, q)


# ---------------------------------------------------------------------------
# Distributed IVF k-means training: THE training step.
# Data-parallel Lloyd iteration: local assign (matmul on MXU), local
# segment-sum, psum-all-reduce of (sums, counts), replicated update.
# ---------------------------------------------------------------------------


def sharded_kmeans_step(mesh: Mesh, X: jnp.ndarray, valid: jnp.ndarray, C: jnp.ndarray):
    """One Lloyd step. X row-sharded (n, d); C replicated (c, d).
    Returns updated replicated centroids."""
    nclusters = C.shape[0]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P()),
        out_specs=P(),
    )
    def _step(X_tile, valid_tile, C_all):
        xsq = (X_tile * X_tile).sum(axis=1)
        csq = (C_all * C_all).sum(axis=1)
        # full f32 on the MXU: the TPU default is one bf16 pass, which
        # would train different centroids than the host does
        d2 = (
            xsq[:, None]
            - 2.0 * jnp.matmul(X_tile, C_all.T, precision="highest")
            + csq[None, :]
        )
        assign = jnp.argmin(d2, axis=1)
        w = valid_tile.astype(X_tile.dtype)
        sums = jax.ops.segment_sum(
            X_tile * w[:, None], assign, num_segments=nclusters
        )
        cnts = jax.ops.segment_sum(w, assign, num_segments=nclusters)
        sums = jax.lax.psum(sums, "data")
        cnts = jax.lax.psum(cnts, "data")
        return jnp.where(
            cnts[:, None] > 0, sums / jnp.maximum(cnts, 1.0)[:, None], C_all
        )

    return _step(X, valid, C)


def sharded_ivf_train(
    mesh: Mesh, X: np.ndarray, nlist: int, iters: int = 10
) -> np.ndarray:
    """Full distributed k-means: shard rows over the mesh, iterate the
    jitted Lloyd step. Returns trained centroids (host numpy)."""
    n, d = X.shape
    ndev = mesh.devices.size
    pad = (-n) % ndev
    Xp = np.concatenate([X, np.zeros((pad, d), X.dtype)]) if pad else X
    valid = np.concatenate([np.ones((n,), bool), np.zeros((pad,), bool)])

    sh = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    Xd = jax.device_put(jnp.asarray(Xp), sh)
    Vd = jax.device_put(jnp.asarray(valid), sh)
    rng = np.random.default_rng(0)
    C = jax.device_put(
        jnp.asarray(X[rng.choice(n, min(nlist, n), replace=False)]), rep
    )
    step = jax.jit(
        lambda x, v, c: sharded_kmeans_step(mesh, x, v, c),
        out_shardings=rep,
    )
    for _ in range(iters):
        C = step(Xd, Vd, C)
    return np.asarray(C)
