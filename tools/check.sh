#!/usr/bin/env bash
# One-shot verification gate: static analysis + tests.
#
#   tools/check.sh          lint + analyzer/registry tests + smoke subset
#   tools/check.sh --full   lint + the FULL tier-1 suite (same command the
#                           ROADMAP pins for tier-1 verify)
#   tools/check.sh --ops-sanity
#                           the ~5s ops-plane gate alone: backup/restore
#                           crash-consistency + CDC ordering/replay
#                           (tests/test_ops_plane.py)
#   tools/check.sh --read-chaos-sanity
#                           the read-plane chaos gate alone: fixed-seed
#                           chaos soak slice — leader SIGKILL under the
#                           bank + query mix, follower-served responses
#                           byte-checked against a leader-routed control
#                           replay (tools/chaos_soak.py --sanity)
#   tools/check.sh --race-sanity
#                           GIL-fuzz race slice (~30s): re-runs the
#                           fixed-seed concurrency suites (group commit,
#                           apply shards, follower reads, serving front,
#                           native-thread stress) with
#                           DGRAPH_TPU_RACE_FUZZ=1, which pins
#                           sys.setswitchinterval(1e-6) so latent
#                           Python-level races surface deterministically
#   tools/check.sh --san-matrix
#                           the full sanitizer matrix (SLOW: recompiles
#                           the native library 3x and re-runs whole
#                           corpora): UBSan + ASan over the byte-equality
#                           corpus, TSan over the threaded kernel stress
#                           corpus, plus the seeded-defect proofs that
#                           each sanitizer actually detects its class
#                           (tests/test_native_san.py)
#
# Exit code is nonzero on the first failing stage, so CI can consume it
# directly. JAX is pinned to CPU: the gate must never dial an accelerator.

set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

if [[ "${1:-}" == "--ops-sanity" ]]; then
    echo "== ops-plane sanity (~5s): backup/restore crash consistency + CDC =="
    python -m pytest tests/test_ops_plane.py -q -p no:cacheprovider
    echo "check.sh: ops-sanity passed"
    exit 0
fi

if [[ "${1:-}" == "--read-chaos-sanity" ]]; then
    echo "== read-plane chaos sanity: leader kill + byte-identity replay =="
    python tools/chaos_soak.py --sanity
    echo "check.sh: read-chaos-sanity passed"
    exit 0
fi

if [[ "${1:-}" == "--race-sanity" ]]; then
    echo "== GIL-fuzz race slice (~30s): switchinterval=1e-6 concurrency suites =="
    DGRAPH_TPU_RACE_FUZZ=1 python -m pytest \
        tests/test_group_commit.py tests/test_batch_apply.py \
        tests/test_follower_reads.py tests/test_serving_front.py \
        tests/test_native_threads.py \
        -q -m 'not slow' -p no:cacheprovider
    echo "check.sh: race-sanity passed"
    exit 0
fi

if [[ "${1:-}" == "--san-matrix" ]]; then
    echo "== sanitizer matrix (slow): ubsan + asan corpus, tsan threaded =="
    python -m pytest tests/test_native_san.py -q -m slow \
        -p no:cacheprovider
    echo "check.sh: san-matrix passed"
    exit 0
fi

# analyzers FIRST: a registry violation (undeclared metric/config, new
# allowlist entry) must fail in seconds, before lint and long before the
# smoke subset gets a chance to run
echo "== analyzer + config-registry self-tests =="
python -m pytest tests/test_static_analysis.py -q -p no:cacheprovider

echo "== dgraph-tpu lint =="
python -m dgraph_tpu.cli lint

if [[ "${1:-}" == "--full" ]]; then
    echo "== full tier-1 suite =="
    python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly
else
    echo "== tier-1 smoke subset =="
    python -m pytest \
        tests/test_setops.py tests/test_uidpack.py \
        tests/test_packed_setops.py tests/test_bitmap_setops.py \
        tests/test_posting.py \
        tests/test_storage.py tests/test_raft.py \
        tests/test_replicated_zero.py tests/test_cluster_facade.py \
        tests/test_tablet_move.py \
        tests/test_observability.py tests/test_distributed_tracing.py \
        tests/test_serving_front.py \
        tests/test_stream_encoder.py \
        tests/test_vector_quant.py \
        tests/test_group_commit.py \
        tests/test_batch_apply.py \
        tests/test_explain.py tests/test_telemetry.py \
        tests/test_planner.py \
        tests/test_ops_plane.py \
        tests/test_follower_reads.py \
        tests/test_flight_recorder.py \
        -q -p no:cacheprovider

    echo "== proc-shard chaos smoke: worker SIGKILL + respawn, ledger exact =="
    python -m pytest tests/test_batch_apply.py -q -m chaos \
        -p no:cacheprovider

    echo "== read-plane chaos sanity: leader kill + byte-identity replay =="
    python tools/chaos_soak.py --sanity
fi

echo "check.sh: all stages passed"
