"""Pallas TPU kernel for the membership hot loop.

The XLA path (ops/setops.py) lowers membership to searchsorted — binary
search with gathers. This kernel reformulates small-side membership as a
*compare-all sweep*: the query set (<=128 uids, one VREG lane row) is
compared against every 8x128 tile of the big sorted list with pure VPU
broadcasting — zero gathers, zero data-dependent control flow (the
reference's IntersectWith ratio>32 regime, algo/uidlist.go:156).

Off by default (DGRAPH_TPU_PALLAS). It compiles with Mosaic and matches
numpy on a TPU v5e (jax 0.9.0, libtpu 0.0.34) at 256 rows x <=128
queries vs 2^20 — and is far from its bandwidth bound there: 323 ms per
batch against 15.5 ms for the vmapped XLA searchsorted on the same
operands (PERF.md, PR 21). One grid step per 4 KiB tile is the likely
cost; it has not been profiled.

The kernel is written BATCH-AWARE (grid = (batch, b_tiles), block specs
indexed by batch) rather than as a vmapped single example: Pallas TPU
lowering rejects the Squeezed SMEM blocks that jax.vmap produces for the
scalar length operand (interpret mode accepts them).

Grid: for each batch row, one step per b-tile; the hit-mask accumulates
across steps via output revisiting (out block index is constant in the
tile dimension). TPU grids iterate the last axis fastest, so the
`step == 0` init runs before that row's accumulation.

The tests run it in interpret mode on CPU; on a TPU it always runs
compiled. The dispatcher uses this path for intersect buckets with
<=128-element small sides when DGRAPH_TPU_PALLAS=1 (query/dispatch.py).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
TILE = LANE * SUBLANE  # 1024 u32 per b-tile


def _default_interpret() -> bool:
    """Compiled on a TPU, always; the interpreter only where Mosaic does
    not exist (the CPU the tests ask for)."""
    from dgraph_tpu.x import device

    return device.platform() != "tpu"


def _member_kernel(lb_ref, a_ref, b_ref, out_ref):
    """One grid step: OR membership hits of batch row i's queries (1,128)
    against its b tile (8,128).

    b-lane validity is computed from the global flat index vs lb (no
    sentinel collisions possible — 0xFFFFFFFF stays a legal uid)."""
    i = pl.program_id(0)
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    a = a_ref[0, 0]  # (LANE,)
    b = b_ref[0]  # (SUBLANE, LANE)
    base = step * TILE
    flat = (
        base
        + jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 0) * LANE
        + jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 1)
    )
    # validity folded in as an i32 multiply: Mosaic cannot insert a minor
    # dim on 1-bit vectors (valid[:, :, None] fails to compile), and the
    # accumulator is i32 for the same reason
    vmask = (flat < lb_ref[i]).astype(jnp.int32)
    # compare-all: (SUBLANE, LANE, 1) vs (1, 1, LANE) -> any over b axes
    eq = (b[:, :, None] == a[None, None, :]).astype(jnp.int32)
    hits = (eq * vmask[:, :, None]).max(axis=(0, 1))
    out_ref[:] = jnp.maximum(out_ref[:], hits[None, None, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _membership_padded(LB, A128, Bp, interpret: bool = False):
    """A128: (n, LANE) u32; Bp: (n, nb*SUBLANE, LANE) u32 row-major tiles;
    LB: (n,) i32 valid lengths. Returns (n, LANE) bool hit masks."""
    n, nbs, _ = Bp.shape
    nb = nbs // SUBLANE
    # (1, 1, LANE) blocks: TPU lowering requires the last two block dims
    # divisible by (8, 128) OR equal to the array dims — a leading
    # singleton axis makes the (1, LANE) row block legal
    out = pl.pallas_call(
        _member_kernel,
        grid=(n, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, LANE), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, SUBLANE, LANE), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, LANE), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, LANE), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(LB, jnp.int32), A128[:, None, :], Bp)
    return out[:, 0, :] != 0


def membership_batch(A, LA, B, LB, interpret=None):
    """Batched membership masks: A (n, pa<=128) u32 sorted rows (padded
    with UINT32_MAX), B (n, pb) u32 sorted rows, lengths LA/LB. Returns
    (n, pa) bool — True where A[i,j] occurs in B[i, :LB[i]]."""
    if interpret is None:
        interpret = _default_interpret()
    n, pa = A.shape
    if pa > LANE:
        raise ValueError(f"pallas membership path is for <=128 queries, got {pa}")
    pb = B.shape[1]
    if pb == 0:
        return jnp.zeros((n, pa), jnp.bool_)
    A_l = jnp.pad(A, ((0, 0), (0, LANE - pa)))
    Bp = jnp.pad(B, ((0, 0), (0, (-pb) % TILE)))
    Bp = Bp.reshape(n, -1, LANE)
    hits = _membership_padded(LB, A_l, Bp, interpret=interpret)
    la_mask = (
        jax.lax.broadcasted_iota(jnp.int32, (n, pa), 1)
        < jnp.asarray(LA, jnp.int32)[:, None]
    )
    return hits[:, :pa] & la_mask


def intersect_batch(A, LA, B, LB, interpret=None):
    """Batched pallas intersect with the same (out, cnt) contract as
    jax.vmap(setops.intersect) — the dispatcher's bucket entry point."""
    from dgraph_tpu.ops import setops

    keep = membership_batch(A, LA, B, LB, interpret=interpret)
    return jax.vmap(setops.compact)(A, keep)


def membership(a, la, b, lb, interpret=None):
    """Single-example membership (<=128 queries) — test/compat shim over
    the batched kernel."""
    mask = membership_batch(
        a[None, :], jnp.asarray([la]), b[None, :], jnp.asarray([lb]),
        interpret=interpret,
    )
    return mask[0]


def intersect(a, la, b, lb, interpret=None):
    """Pallas-backed intersect for small a (uses sort-based compaction)."""
    from dgraph_tpu.ops import setops

    keep = membership(a, la, b, lb, interpret=interpret)
    return setops.compact(a, keep)
