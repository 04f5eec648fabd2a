"""dgraph_tpu: a TPU-native distributed graph database framework.

A from-scratch rebuild of the capabilities of dgraph-io/dgraph (reference at
/root/reference): predicate-sharded posting lists, MVCC transactions with a
Zero-style oracle, DQL query execution, full-text/geo/vector indexing, loaders,
backup/export — with the hot query kernels (sorted-UID set algebra, batched
per-predicate task fan-out, vector top-k) redesigned as batched JAX/XLA
kernels running on TPU.

Layer map (mirrors SURVEY.md §1):
  ops/      — device kernels: sorted-set algebra, top-k    (ref: algo/, codec/)
  codec/    — UID pack block codec, host<->device format   (ref: codec/codec.go)
  x/        — key layout, config, errors                   (ref: x/)
  types/    — scalar types & conversion                    (ref: types/)
  tok/      — tokenizer registry                           (ref: tok/)
  schema/   — schema parser & state                        (ref: schema/)
  storage/  — host KV store (badger equivalent)            (ref: badger dep)
  posting/  — MVCC posting lists, local cache              (ref: posting/)
  zero/     — ts/UID leasing, txn oracle                   (ref: dgraph/cmd/zero)
  dql/      — DQL lexer + parser                           (ref: lex/, dql/)
  query/    — SubGraph executor w/ batched device dispatch (ref: query/, worker/task.go)
  models/   — vector index families (brute/IVF)            (ref: tok/hnsw)
  parallel/ — mesh, shardings, distributed kernels         (ref: conn/, worker sharding)
  loaders/  — RDF/JSON chunker, bulk/live loaders          (ref: chunker/, cmd/bulk, cmd/live)
  api/      — transaction/API front-end                    (ref: edgraph/)
"""

__version__ = "0.1.0"

# Persistent XLA compile cache. Where JAX_COMPILATION_CACHE_DIR is set
# it is used untouched; where it is not, the cache lives at ONE fixed,
# git-ignored path inside the checkout — the directory is part of the
# cache key, so a path that moves between runs never hits. jax reads the
# variable at `import jax`: entry points import dgraph_tpu first.
import os as _os

if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    _os.environ["JAX_COMPILATION_CACHE_DIR"] = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache",
    )
# query shapes are pow2-bucketed and most kernels compile in under a
# second: cache every entry, not only the slow ones
_os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
_os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
del _os
