"""Device programs over a resident VALUE column: one predicate's values
as (sorted uint32 uids, one int32 order key each), the key being the
dense rank of the value among the column's distinct values, so that
`key_a < key_b` iff `value_a < value_b` as the comparator orders them
(query/valcol.py builds it; the reference reads every candidate's value
one posting list at a time: worker/task.go handleCompareFunction's
filter loop, worker/sort.go sortWithoutIndex).

The candidates come as ONE flat uint32 array padded with UINT32_MAX
plus their real count, in any order and with repeats (a level's rows
laid end to end, as `setops.membership`'s flat form takes them): each
is looked up on its own. Two uses:

- `column_filter`: mask of the candidates whose key lies in [lo, hi].
  The host turns a bound (lt/le/gt/ge/between a value) into that rank
  range with one searchsorted over the distinct values. A candidate the
  column does not hold has no value and fails, as the filter loop has it.
- `column_narrow`: under `order .. first: N`, the candidates whose key
  is at or beyond the `need`-th in the key's direction, ties with it
  INCLUDED: every id left out sorts strictly after `need` kept ones
  whatever the later keys say, so the comparator over the kept ids
  gives the exact window. Where fewer than `need` candidates have a
  value, ids with none reach the window too and every candidate is
  kept (what `_narrow_to_window` calls "refilled").

`window_keep` is the shared cut, over any keys that are weakly monotone
in the order wanted (ranks here; float32 scores of a value variable in
`Executor._order_uids_topk`, where rounding only adds ties).

Static shapes, no data-dependent control flow; `need`, the bounds and
the direction are runtime scalars, so one program per padded shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dgraph_tpu.ops import setops


def gather_keys(cand, n, uids, rows, keys):
    """(hit, key): hit[i] = i < n and cand[i] is one of uids[:rows];
    key[i] = that row's key (unspecified where not hit)."""
    idx = setops._searchsorted(uids, cand)
    idx_c = jnp.minimum(idx, uids.shape[0] - 1)
    hit = (idx < rows) & (jnp.take(uids, idx_c) == cand)
    return hit & setops._iota_mask(cand.shape[0], n), jnp.take(keys, idx_c)


def column_filter(cand, n, uids, rows, keys, lo, hi):
    """mask[i] = candidate i has a value whose rank is in [lo, hi]."""
    hit, key = gather_keys(cand, n, uids, rows, keys)
    return hit & (key >= lo) & (key <= hi)


def _ordered_bits(key):
    """`key` as uint32 that order as the keys do: an int32 with its sign
    bit flipped; a float32 by the usual bit trick (negatives reversed;
    -0.0 made 0.0 first, its equal), NaN nowhere in particular."""
    if jnp.issubdtype(key.dtype, jnp.floating):
        key = key.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(
            jnp.where(key == 0, jnp.float32(0), key), jnp.int32
        )
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(
        key.astype(jnp.int32), jnp.uint32
    ) ^ jnp.uint32(0x80000000)


def window_keep(key, valid, n, need):
    """(keep, valued): larger `key` is better. keep = the valid
    elements at or beyond the `need`-th best key, ties included; where
    fewer than `need` are valid, every element under `n`. valued = how
    many are valid. The cut is found bit by bit from the top, 32 counts
    over the keys: the greatest t with at least `need` valid keys >= t
    is the `need`-th best key (a sort would say the same and takes the
    chip's compiler 11 s where this takes one: PERF.md, PR 36)."""
    k = _ordered_bits(key)

    def step(i, cut):
        bit = jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32))
        reach = jnp.sum(valid & (k >= (cut | bit)), dtype=jnp.int32)
        return jnp.where(reach >= need, cut | bit, cut)

    cut = jax.lax.fori_loop(0, 32, step, jnp.uint32(0))
    valued = jnp.sum(valid, dtype=jnp.int32)
    keep = jnp.where(
        valued < need, setops._iota_mask(key.shape[0], n), valid & (k >= cut)
    )
    return keep, valued


def column_narrow(cand, n, uids, rows, keys, need, desc):
    """(keep, valued) for a leading order key: ranks ascend with the
    value, so ascending order wants the smallest."""
    hit, key = gather_keys(cand, n, uids, rows, keys)
    return window_keep(jnp.where(desc, key, -key), hit, n, need)


def scores_narrow(score, n, need):
    """(keep, valued) over float scores, NaN marking "no value"
    (`Executor._order_uids_topk`)."""
    valid = setops._iota_mask(score.shape[0], n) & ~jnp.isnan(score)
    return window_keep(score, valid, n, need)


KERNELS = {
    "filter": column_filter,
    "narrow": column_narrow,
    "scores": scores_narrow,
}
