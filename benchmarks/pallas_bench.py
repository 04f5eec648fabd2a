"""Pallas compare-all sweep vs XLA searchsorted on the live backend.

The dispatcher's small-side intersect path has two device formulations:
  - setops.intersect: searchsorted (binary search + gather)
  - pallas_setops.intersect: compare-all VPU sweep (ops/pallas_setops.py)

This benchmark runs both COMPILED on a TPU (the Pallas interpreter
elsewhere) over the reference's ratio ladder
(/root/reference/algo/benchmarks shapes: small=10..128 vs big=10k..4M)
and reports per-op ns for a 128-wide vmapped batch, so the dispatcher's
_USE_PALLAS default can be set from data instead of a guess.

Usage: python benchmarks/pallas_bench.py [--json out]
"""

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:  # `python benchmarks/x.py` puts only benchmarks/ there
    _sys.path.insert(0, _REPO)

import dgraph_tpu  # noqa: E402,F401 — places the compile cache before jax loads

import argparse
import json
import time

import numpy as np


def _bench(fn, args, iters=30):
    # warmup + compile
    out = fn(*args)
    _block(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _block(out)
    return (time.perf_counter() - t0) / iters


def _block(out):
    import jax

    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
        out,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops import setops, pallas_setops

    backend = jax.default_backend()
    interpret = backend != "tpu"
    rng = np.random.default_rng(7)
    batch = args.batch

    rows = []
    for small, big in [
        (10, 10_000),
        (10, 100_000),
        (10, 1_000_000),
        (128, 100_000),
        (128, 1_000_000),
        (128, 4_000_000),
    ]:
        pa = max(8, 1 << (small - 1).bit_length())
        pb = 1 << (big - 1).bit_length() if big & (big - 1) == 0 else 1 << big.bit_length()
        B = np.full((batch, pb), setops.UINT32_MAX, np.uint32)
        A = np.full((batch, pa), setops.UINT32_MAX, np.uint32)
        for i in range(batch):
            b = np.sort(
                rng.choice(np.uint32(1) << np.uint32(31), size=big, replace=False)
            ).astype(np.uint32)
            a = np.sort(rng.choice(b, size=small, replace=False)).astype(np.uint32)
            B[i, :big] = b
            A[i, :small] = a
        LA = np.full((batch,), small, np.int32)
        LB = np.full((batch,), big, np.int32)
        Ad, Bd = jnp.asarray(A), jnp.asarray(B)
        LAd, LBd = jnp.asarray(LA), jnp.asarray(LB)

        xla_fn = jax.jit(jax.vmap(setops.intersect))
        t_xla = _bench(xla_fn, (Ad, LAd, Bd, LBd))

        t_pallas = None
        if small <= 128:
            def pl_batch(A_, LA_, B_, LB_):
                return pallas_setops.intersect_batch(
                    A_, LA_, B_, LB_, interpret=interpret
                )

            # a Mosaic refusal raises: a kernel that cannot compile is a
            # failed run, not a null in the table
            t_pallas = _bench(jax.jit(pl_batch), (Ad, LAd, Bd, LBd))

        row = {
            "small": small,
            "big": big,
            "batch": batch,
            "xla_ns_per_op": round(t_xla / batch * 1e9, 1),
            "pallas_ns_per_op": (
                round(t_pallas / batch * 1e9, 1) if t_pallas is not None else None
            ),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    result = {"backend": backend, "interpret": interpret, "rows": rows}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({"summary": result}, indent=1))


if __name__ == "__main__":
    main()
