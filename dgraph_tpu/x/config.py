"""Typed registry for every `DGRAPH_TPU_*` environment knob.

Before this module each knob was a raw `os.environ.get` at its call
site, with the default duplicated (and free to drift) per site and no
single place documenting what exists. This registry is now the ONLY
sanctioned reader of `DGRAPH_TPU_*` variables — the static-analysis
suite (`dgraph_tpu/analysis`, `dgraph-tpu lint`) flags any raw
`os.environ` / `os.getenv` access elsewhere in the package.

Contract:

  - Every knob is declared ONCE here with (name, type, default, doc).
  - `get("NAME")` reads `DGRAPH_TPU_<NAME>` from the environment,
    parses it to the declared type, and falls back to the declared
    default when unset OR unparseable (a malformed value must never
    crash a server at import time).
  - Booleans accept 1/true/yes/on and 0/false/no/off (case-insensitive);
    anything else falls back to the default.
  - `reference_table()` renders the whole registry as the Markdown
    table checked in at CONFIG.md (tests assert the file is in sync).

Call sites keep their own read-at-import vs read-per-call timing; this
module only centralizes the parse + default.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional

PREFIX = "DGRAPH_TPU_"

_TRUE = frozenset({"1", "true", "yes", "on"})
_FALSE = frozenset({"0", "false", "no", "off"})


@dataclass(frozen=True)
class Knob:
    name: str  # short name; env var is PREFIX + name
    type: str  # "str" | "int" | "float" | "bool"
    default: Any
    doc: str

    @property
    def env(self) -> str:
        return PREFIX + self.name

    def parse(self, raw: str) -> Any:
        """Parse a raw env string; raises ValueError when malformed."""
        if self.type == "str":
            return raw
        if self.type == "bool":
            v = raw.strip().lower()
            if v in _TRUE:
                return True
            if v in _FALSE:
                return False
            raise ValueError(f"{self.env}={raw!r} is not a boolean")
        if self.type == "int":
            return int(raw.strip())
        if self.type == "float":
            return float(raw.strip())
        raise ValueError(f"unknown knob type {self.type!r}")


REGISTRY: Dict[str, Knob] = {}


def _define(name: str, type_: str, default: Any, doc: str) -> Knob:
    if name in REGISTRY:
        raise ValueError(f"duplicate knob {name!r}")
    k = Knob(name=name, type=type_, default=default, doc=doc)
    REGISTRY[name] = k
    return k


# ---------------------------------------------------------------------------
# knob declarations (one line per knob; keep alphabetical)
# ---------------------------------------------------------------------------

_define(
    "ADMISSION", "bool", False,
    "Admission control at the query entry points (serving/admission.py): "
    "estimated query cost is charged against DGRAPH_TPU_MAX_INFLIGHT "
    "tokens; over-budget arrivals are shed fast with a retryable "
    "too_many_requests error (HTTP 429), and arrivals during saturation "
    "(slow-query signal or exec-pool backpressure) run degraded — "
    "bounded budget, partial response — instead of queueing. Off by "
    "default; the in-flight gauge is tracked regardless.",
)
_define(
    "APPLY_PROCS", "str", "auto",
    "Multi-process apply shards behind the raft apply loop "
    "(worker/applyshard.py): the group-commit columnar write-set is "
    "partitioned by (namespace, predicate) and shipped over per-worker "
    "shared-memory rings to this many apply-shard worker processes, "
    "whose batch_apply kernels run outside the serving interpreter's "
    "GIL. 'auto' resolves to cpu_count-1; 0 is the in-process escape "
    "hatch (the kernel runs on the committing thread, exactly the "
    "pre-proc path).",
)
_define(
    "APPLY_PROC_TIMEOUT_MS", "int", 5000,
    "Per-batch deadline (ms) for an apply-shard worker process to "
    "return its encoded shard (worker/applyshard.py): a worker that "
    "blows it is killed and respawned, and the batch replays through "
    "the in-process kernel with exact serial semantics "
    "(apply_shard_fallback_total{reason=\"timeout\"}).",
)
_define(
    "APPLY_RING_BYTES", "int", 16 << 20,
    "Size of each apply-shard worker's shared-memory ring "
    "(worker/applyshard.py): one flat request/response region the "
    "columnar batch columns are memcpy'd into (no pickling of edges). "
    "A batch whose columns or encoded output exceed it falls back to "
    "the in-process kernel (reason=\"ring_full\").",
)
_define(
    "APPLY_SHARDS", "int", 0,
    "Predicate-sharded residual mutation apply (posting/mutation.py "
    "_apply_edges_sharded): edges that escape the columnar kernel are "
    "partitioned by (namespace, predicate) and applied concurrently on "
    "the exec-worker pool, merged back deterministically in shard-index "
    "order (all key kinds embed the attr, so shards touch disjoint "
    "keys). 0 (default) = automatic — shard when EXEC_WORKERS >= 2 and "
    "the call clears DGRAPH_TPU_APPLY_SHARD_MIN_EDGES; 1 forces the "
    "serial path; N>1 forces up to N shards regardless of size.",
)
_define(
    "APPLY_SHARD_MIN_EDGES", "int", 64,
    "Minimum edges in one apply_edges call before the automatic "
    "predicate-sharding heuristic engages (posting/mutation.py): below "
    "this, thread handoff costs more than the GIL-released tokenizer "
    "work the shards would overlap.",
)
_define(
    "BACKUP_CHUNK_BYTES", "int", 4 << 20,
    "Byte bound on one backup chunk file's (uncompressed) record "
    "payload (admin/backup.py BackupWriter): a tablet of any size "
    "streams into bounded, individually-verifiable files instead of "
    "one unbounded stream a torn write could silently shorten.",
)
_define(
    "BATCH_APPLY", "bool", True,
    "Columnar native mutation apply (posting/colwrite.py + codec.cpp "
    "batch_apply): fast-shape SET edges (scalar values with "
    "exact/int/bool/term indexes, list-uid incl. @reverse) are "
    "collected as columns instead of Posting objects and encoded at "
    "commit by ONE native call per group-commit batch — fused "
    "tokenization, index/reverse key emission and delta-record "
    "encoding, byte-identical to the serial path. Ineligible edges "
    "materialize back through the serial path automatically. 0 "
    "restores the per-edge Python apply everywhere — the A/B escape "
    "hatch.",
)
_define(
    "BATCH_WINDOW_US", "int", 0,
    "Cross-query micro-batching (serving/microbatch.py): same-shape "
    "(predicate, level) tasks from different in-flight queries that "
    "arrive DURING an in-flight same-shape dispatch coalesce into the "
    "next combined level read, demuxed per query on return (natural "
    "batching: an idle shape dispatches immediately with zero added "
    "latency). The value caps, in microseconds, how long a forming "
    "batch waits for the dispatch ahead of it. 0 (default) disables "
    "the batcher entirely — the executor takes the direct path.",
)
_define(
    "BITMAP_BLOCK_BITS", "int", 2048,
    "Fixed bitset size (bits, rounded up to a multiple of 64) for the "
    "per-block bitmap containers: a UidPack block whose uid range fits "
    "and whose density clears 1/8 materializes as a bitset and runs the "
    "word-wise AND/ANDNOT kernels (codec/uidpack.py, native/codec.cpp); "
    "dense blocks also serialize as raw bitsets. 0 disables bitmap "
    "containers entirely — use in a mixed-version store, since records "
    "holding bitmap blocks are unreadable by pre-bitmap builds.",
)
_define(
    "BULK_NATIVE", "bool", True,
    "Use the native C++ map/reduce pipeline for offline bulk loads when "
    "the compiled library is available (loaders/bulk2.py). Disable to "
    "force the pure-Python slow path.",
)
_define(
    "CDC_QUEUE_MAX", "int", 4096,
    "Bounded CDC event queue (admin/cdc.py): commits enqueue their "
    "events here for the sink-emitter thread; a full queue blocks the "
    "committer (backpressure) until the sink drains, so an event can "
    "never be silently dropped while the process lives. Sink-crash "
    "loss windows are closed by replay-from-checkpoint at startup.",
)
_define(
    "CDC_SINK", "str", "",
    "Default CDC sink URI for `dgraph-tpu alpha`/`cdc` when no "
    "explicit sink is given: a file path / file:// URI (ndjson), or "
    "kafka://host:port/topic when kafka-python is installed "
    "(admin/handlers.py sink_for). Empty = CDC disabled unless "
    "enabled explicitly.",
)
_define(
    "COMMIT_DEADLINE_S", "float", 20.0,
    "Budget stamped on a commit at the ProcCluster entry point; flows "
    "through zero.commit and every group proposal beneath it "
    "(worker/harness.py).",
)
_define(
    "DEBUG_HTTP", "bool", True,
    "Serve /debug/prometheus_metrics + /debug/traces over HTTP from "
    "every alpha/zero replica process (ephemeral port, discoverable "
    "via the debug.info RPC). 0 disables the per-process listener.",
)
_define(
    "DEVCACHE_BYTES", "int", 256 << 20,
    "LRU bound, in device bytes, for the HBM operand cache "
    "(query/dispatch.py DeviceCache).",
)
_define(
    "DEVICE_MIN_TOTAL", "int", None,
    "Min combined operand size routed to the device kernels. Unset = "
    "by platform (host-only when JAX_PLATFORMS=cpu asked for the CPU, "
    "1<<15 on an accelerator); 0 means ALWAYS use the device "
    "(query/dispatch.py).",
)
_define(
    "DIGEST", "bool", True,
    "Always-on query digest store (serving/digest.py): per-(namespace, "
    "normalized-shape) aggregate statistics — calls, errors, latency "
    "histogram, result rows/bytes, plan/result-cache hits, packed-"
    "kernel deltas — fed at the query entry points and served at "
    "/debug/digests (the pg_stat_statements analog). 0 disables the "
    "accounting — the flight-recorder A/B escape hatch.",
)
_define(
    "DIGEST_SHAPES", "int", 512,
    "Digest-store capacity in distinct (namespace, shape) rows "
    "(serving/digest.py); LRU beyond it, with evicted rows folded "
    "into the sticky per-namespace `other` bucket so totals stay "
    "exact under churn.",
)
_define(
    "EXEC_WORKERS", "int", 0,
    "Sibling fan-out width for the parallel query executor; 0/1 = "
    "serial escape hatch (query/subgraph.py). Re-read per Executor so "
    "tests can flip it between queries.",
)
_define(
    "EXEMPLARS", "bool", True,
    "Trace exemplars on latency histograms: each histogram bucket "
    "retains its latest (value, trace_id) observation, exported in "
    "OpenMetrics exemplar syntax at /debug/openmetrics and embedded in "
    "slow-query log records — the metrics→trace link "
    "(utils/observe.py). 0 disables exemplar capture.",
)
_define(
    "FAKE_NOW", "str", "",
    "Frozen timestamp for @default($now) GraphQL values — test "
    "determinism hook (graphql/resolve.py). Empty = real UTC now.",
)
_define(
    "FAULT_PLAN", "str", "",
    "Deterministic fault-injection plan: inline JSON or @/path/to/file "
    "(conn/faults.py). Inherited by alpha/zero replica processes.",
)
_define(
    "FORCE_DEVICE", "bool", False,
    "Route every set op to the device kernels regardless of size "
    "thresholds (query/dispatch.py) — benchmarking hook.",
)
_define(
    "FOLLOWER_READS", "bool", True,
    "Watermark-verified follower read routing (worker/remote.py, "
    "worker/groups.py): read-only calls may be served by any replica "
    "whose raft applied index covers the query's snapshot watermark "
    "(PR 11 rule — provably byte-identical), picked by latency EWMA "
    "with a per-replica circuit breaker; a leaderless group keeps "
    "serving watermark reads marked `degraded: leaderless` (only once "
    "the read floor is KNOWN — a restarted coordinator serves "
    "leader-only until a leader reply/proposal re-establishes it). 0 "
    "restores strict leader-first routing: the blind follower hedge "
    "on the remote plane, leader-only in-proc.",
)
_define(
    "FOLLOWER_READ_TTL_S", "float", 0.5,
    "Freshness window for a replica's cached applied-index/health row "
    "(worker/replicapick.py): a follower whose row is older than this "
    "is skipped (stale-or-unknown never serves) and a background "
    "re-probe is kicked off.",
)
_define(
    "GROUP_COMMIT", "bool", True,
    "Group-commit write pipeline (worker/groupcommit.py): concurrent "
    "committers coalesce into batches that share ONE oracle verdict "
    "exchange and ONE bounded raft proposal per owning group, with the "
    "snapshot watermark advanced in commit-ts order. 0 restores the "
    "serial per-txn commit path byte-for-byte (the A/B escape hatch).",
)
_define(
    "GROUP_COMMIT_BYPASS", "bool", True,
    "Adaptive group-commit bypass (worker/groupcommit.py): when the "
    "realized batch-width EWMA is ~1 (no batchmate is ever waiting) a "
    "committer that finds the coalescer completely idle commits "
    "straight through the engine's serial path, skipping the "
    "queue/ticket/condvar handoffs that measurably lose to serial at "
    "width ~1.05. Concurrency re-engages coalescing automatically "
    "(an arrival during a bypass or a busy leader always queues). 0 "
    "forces every commit through the coalescer (the A/B escape "
    "hatch).",
)
_define(
    "GROUP_COMMIT_MAX_TXNS", "int", 64,
    "Cap on transactions coalesced into one commit batch "
    "(worker/groupcommit.py); excess committers form the next batch.",
)
_define(
    "GROUP_COMMIT_WINDOW_US", "int", 200,
    "Extra microseconds a commit-batch leader waits for more "
    "committers to arrive — only while an earlier batch's apply "
    "barrier is still in flight (an idle engine always commits "
    "immediately, like the PR 7 batcher's natural batching). 0 "
    "disables the wait; batches still form from whatever is queued.",
)
_define(
    "HISTORY", "bool", True,
    "Metrics history ring (utils/observe.py MetricsHistory): a "
    "background sampler snapshots every counter/gauge + histogram "
    "sum/count once per HISTORY_INTERVAL_S into a bounded in-memory "
    "ring, so windowed deltas (/debug/history?window=) are computable "
    "after an incident without reruns. 0 disables sampling — the "
    "flight-recorder A/B escape hatch.",
)
_define(
    "HISTORY_DIR", "str", "",
    "When set, history snapshots are also appended to an on-disk ring "
    "(history-<instance|pid>.log inside this directory) in the shared "
    "AppendLog record format — torn tails truncated at open, rotation "
    "at HISTORY_DISK_MAX_BYTES — so the recorded window survives a "
    "process restart. Empty = in-memory only.",
)
_define(
    "HISTORY_DISK_MAX_BYTES", "int", 8 << 20,
    "Rotation bound for the on-disk history ring: past it the file is "
    "rewritten keeping the newest half of its records (the slow-query-"
    "log hysteresis, amortized rewrites).",
)
_define(
    "HISTORY_INTERVAL_S", "float", 60.0,
    "Seconds between metrics-history snapshots (minute buckets by "
    "default; tests dial it down).",
)
_define(
    "HISTORY_RETENTION", "int", 180,
    "In-memory history snapshots retained (oldest dropped beyond it): "
    "180 x 60s = a 3h window at the default interval.",
)
_define(
    "LAMBDA_URL", "str", "",
    "GraphQL @lambda resolver endpoint; the alpha CLI superflag takes "
    "precedence (graphql/resolve.py).",
)
_define(
    "LEVEL_BATCH", "bool", True,
    "Level-batched task reads (uids_many/values_many, one MemoryLayer "
    "pass per level). 0 = per-uid escape hatch for A/B benchmarking "
    "(query/subgraph.py).",
)
_define(
    "MAX_FRAME_BYTES", "int", 256 << 20,
    "Hard cap on a single wire frame on BOTH the RPC and raft planes; "
    "a corrupt length prefix must never drive an unbounded allocation "
    "(conn/frame.py, matches the reference's 256MB gRPC cap).",
)
_define(
    "MAX_INFLIGHT", "int", 64,
    "Admission-control in-flight budget, in cost tokens (one token ~ "
    "10ms of observed shape latency; selectivity and pool backpressure "
    "add more). Arrivals that would push the in-flight cost past this "
    "are shed with too_many_requests when DGRAPH_TPU_ADMISSION is on "
    "(serving/admission.py).",
)
_define(
    "MAX_PART_UIDS", "int", 1 << 20,
    "Multi-part posting list threshold: a rollup whose uid set exceeds "
    "this splits into part records. ONE default shared by the runtime "
    "split (posting/pl.py) and the native bulk reduce (loaders/"
    "bulk2.py) — these previously duplicated the constant per site.",
)
_define(
    "MEMLAYER_ENTRIES", "int", 400_000,
    "MemoryLayer LRU capacity (decoded posting lists). Must exceed the "
    "touched-key count of one large traversal level or the LRU "
    "thrashes (posting/memlayer.py).",
)
_define(
    "MOVE_CHUNK_BYTES", "int", 4 << 20,
    "Byte bound on one ('delta', chunk) proposal — and on one paged "
    "source-read response — during a phased tablet move "
    "(worker/tabletmove.py): a tablet of any size streams in bounded "
    "chunks instead of one frame-cap-tripping proposal. Must stay "
    "under DGRAPH_TPU_MAX_FRAME_BYTES.",
)
_define(
    "MOVE_FENCE_DEADLINE_S", "float", 10.0,
    "Budget for a tablet move's Phase-2 fence (moving state + delta "
    "catch-up + ownership flip, under the commit lock). A delta stream "
    "that overruns it aborts and rolls the move back, so the fence can "
    "never wedge writers indefinitely (worker/tabletmove.py).",
)
_define(
    "NATIVE_CACHE", "str", None,
    "Directory holding the compiled native kernel library "
    "(native/__init__.py); keyed by source hash + sanitizer mode. "
    "Unset = <system tempdir>/dgraph_tpu_native.",
)
_define(
    "NATIVE_SAN", "str", "",
    "Sanitizer build mode for the native library: 'asan', 'tsan' or "
    "'ubsan' compile the .so with the matching -fsanitize= flags under "
    "a separate cache key; empty = plain -O3. asan/tsan need the "
    "runtime preloaded (LD_PRELOAD=$(g++ -print-file-name=libasan.so / "
    "libtsan.so)) — tests/test_native_san.py and tools/check.sh "
    "--san-matrix handle this (native/__init__.py).",
)
_define(
    "PACKED_MIN_RATIO", "int", 8,
    "Packed-vs-decode crossover for array x pack pairs: the op takes "
    "the compressed-domain path when |big| >= ratio * |small| (query/"
    "dispatch.py; a host-side crossover measured on one CPU core — 8 "
    "with the native adaptive block engine, down from the pre-engine "
    "256). Pack x pack pairs bypass the gate entirely (the pair engine "
    "runs them with zero decode); without the native engine an unset "
    "knob falls back to the pre-engine cliff of 256.",
)
_define(
    "PLAN_CACHE_SIZE", "int", 512,
    "Plan-cache capacity in distinct normalized query shapes (serving/"
    "plancache.py); each shape holds a bounded set of literal-binding "
    "variants whose parsed trees skip parse entirely on a hit. Entries "
    "are invalidated by commit epoch (no plan survives a commit "
    "unrevalidated). 0 disables plan caching; per-shape cost stats for "
    "admission are disabled with it.",
)
_define(
    "PROFILE_AUTO", "bool", True,
    "Auto-trigger the sampling profiler on sustained SLO burn "
    "(utils/profiler.py): when the 300s query burn rate exceeds "
    "PROFILE_BURN at a history tick, a PROFILE_AUTO_S capture runs in "
    "the background and is retained for /debug/profile?last=1 — the "
    "GIL-bound residual gets attributed while it is happening. 0 "
    "disables auto-triggering (on-demand captures still work).",
)
_define(
    "PROFILE_AUTO_S", "float", 5.0,
    "Duration, in seconds, of an auto-triggered profiler capture.",
)
_define(
    "PROFILE_BURN", "float", 2.0,
    "SLO burn-rate threshold (300s window) past which the profiler "
    "auto-triggers; burn 1.0 = exactly consuming the error budget.",
)
_define(
    "PROFILE_COOLDOWN_S", "float", 600.0,
    "Minimum seconds between auto-triggered profiler captures — one "
    "sustained incident must not stack samplers.",
)
_define(
    "PROFILE_HZ", "int", 100,
    "Sampling frequency of the wall-clock profiler "
    "(utils/profiler.py): sys._current_frames() walks per second "
    "while a capture is active. The sampler runs ONLY during a "
    "capture; idle cost is zero.",
)
_define(
    "QUERY_DEADLINE_S", "float", 15.0,
    "Budget stamped on a query at the ProcCluster entry point; flows "
    "through every remote read beneath it (worker/harness.py).",
)
_define(
    "QUERY_PLANNER", "bool", True,
    "Cost-based query planner (query/planner.py): orders AND-filter "
    "chains and var-free sibling expansion cheapest-first from "
    "StatsHolder selectivity + observed-cardinality EWMAs, narrows "
    "later filter arms with the running intersection, and pushes "
    "index-answerable level filters below the fan-out when the match "
    "set is estimated smaller than the frontier. Observation-"
    "equivalent by construction (golden-corpus-enforced byte "
    "identity); 0 restores declaration-order execution — the A/B "
    "escape hatch.",
)
_define(
    "RACE_FUZZ", "bool", False,
    "GIL-fuzz race harness: when set, tests/conftest.py pins "
    "sys.setswitchinterval(1e-6) so the interpreter forces a thread "
    "switch roughly every bytecode, surfacing latent Python-level "
    "races in the fixed-seed concurrency suites deterministically "
    "instead of once a month under full-suite load. Run via "
    "tools/check.sh --race-sanity.",
)
_define(
    "READ_BREAKER_ERRORS", "int", 3,
    "Consecutive read failures that trip a replica's read-plane "
    "circuit breaker OPEN (worker/replicapick.py); an open replica is "
    "skipped by the picker until a jittered half-open probe succeeds. "
    "0 disables the breaker (every replica always eligible).",
)
_define(
    "READ_BREAKER_PROBE_S", "float", 1.0,
    "Mean interval between half-open probes of an OPEN read-plane "
    "breaker (worker/replicapick.py); each probe window is jittered "
    "uniform(0.5x, 1.5x) so a fleet of coordinators de-synchronizes. "
    "Bounds the availability gap after a replica dies: within ~one "
    "probe interval traffic has routed around it.",
)
_define(
    "READ_RETRY_BUDGET", "int", 16,
    "Per-query retry/hedge token budget (conn/retry.py RetryBudget, "
    "carried on the ReadContext): every group-read retry and every "
    "hedge fire across the whole query spends one token, so a "
    "brownout costs at most this many extra RPCs instead of "
    "multiplying per layer. Exhaustion surfaces as a retryable 503 "
    "(read_retry_budget_exhausted_total). 0 disables budgeting.",
)
_define(
    "REBALANCE_BY_TRAFFIC", "bool", False,
    "Auto-rebalance scoring mode: when on, the tablet picker weighs "
    "each tablet by size PLUS its observed traffic (decoded/result "
    "bytes served, mutation-edge volume, from the per-tablet traffic "
    "accumulator), so a hot small tablet can outweigh a cold giant "
    "one (worker/tabletmove.py pick_rebalance_move_by_traffic). Off "
    "by default: size-based rebalance stays the deterministic "
    "baseline.",
)
_define(
    "REBALANCE_INTERVAL_S", "float", 480.0,
    "Mean period of the jittered auto-rebalance loop "
    "(enable_auto_rebalance: each tick heals journaled half-moves, "
    "then takes one size-based tablet move when it narrows the "
    "byte-load gap; uniform(0, 2i) jitter de-synchronizes a fleet). "
    "Matches the reference Zero's ~8-minute rebalance cadence "
    "(zero/tablet.go).",
)
_define(
    "RESULT_CACHE_SIZE", "int", 0,
    "Snapshot-keyed whole-response result cache (serving/"
    "resultcache.py), in entries: responses are keyed on (normalized "
    "plan shape, literal bindings, variables, namespace, snapshot "
    "watermark), so a cached entry is provably byte-identical to "
    "re-execution until a commit advances the watermark — the PR 7/11 "
    "watermark proof (two reads covering the same watermark see "
    "identical stores). 0 (default) disables result reuse, like the "
    "other serving-front gates (ADMISSION, BATCH_WINDOW_US).",
)
_define(
    "RESULT_CACHE_BYTES", "int", 64 << 20,
    "Byte bound on the result cache's stored response payloads "
    "(serving/resultcache.py): eviction runs until BOTH the entry "
    "count (RESULT_CACHE_SIZE) and this byte total are under bound, "
    "so wide-fan-out responses cannot grow the cache past what the "
    "operator sized. 0 disables the byte bound (entry count only).",
)
_define(
    "RESULT_CACHE_TTL_S", "float", 300.0,
    "Age bound on a result-cache entry (serving/resultcache.py): "
    "entries older than this are treated as misses even at an "
    "unchanged watermark (a safety valve for long write-idle "
    "deployments, not a correctness requirement — watermark keying "
    "already guarantees freshness). 0 disables the TTL.",
)
_define(
    "SHARD_MIN_B", "int", 1 << 22,
    "A shared operand at/above this byte size is row-sharded over the "
    "device mesh when >1 device is visible (query/dispatch.py).",
)
_define(
    "SHARD_VECTORS", "bool", False,
    "Row-shard vector similarity corpora over the device mesh "
    "(models/vector.py + parallel/mesh.py sharded_topk).",
)
_define(
    "SKIP_REMOTE_INTROSPECTION", "bool", False,
    "Defer @custom(http:{graphql:...}) remote-endpoint introspection "
    "at schema-update time — air-gapped loads (graphql/resolve.py).",
)
_define(
    "SLO_QUERY_MS", "float", 250.0,
    "SLO latency objective in milliseconds for the entry-point "
    "latency histograms (query_latency_seconds / "
    "commit_latency_seconds): operations slower than this count "
    "against the error budget in the multi-window burn rates served "
    "at /debug/healthz (utils/observe.py SloWindows).",
)
_define(
    "SLO_TARGET", "float", 0.99,
    "SLO availability target (fraction of operations meeting "
    "DGRAPH_TPU_SLO_QUERY_MS): the error budget is 1 - target, and a "
    "window's burn rate is its error rate divided by that budget "
    "(burn 1.0 = consuming budget exactly) (utils/observe.py).",
)
_define(
    "SLOW_QUERY_LOG", "str", "",
    "Path of the bounded slow-query JSONL log (utils/observe.py "
    "SlowQueryLog). Empty = slow operations fall back to a logging "
    "warning; records carry the query text, latency, trace id, and the "
    "force-sampled local span tree.",
)
_define(
    "SLOW_QUERY_LOG_MAX", "int", 1000,
    "Record cap on the slow-query log; once exceeded the file is "
    "rewritten keeping the newest N/2 (hysteresis amortizes the "
    "rewrite over bursts) (utils/observe.py).",
)
_define(
    "SLOW_QUERY_MS", "float", 1000.0,
    "Slow-operation threshold in milliseconds: queries/commits slower "
    "than this are force-sampled (their buffered spans exported even "
    "when the trace was unsampled) and appended to the slow-query log "
    "(utils/observe.maybe_log_slow).",
)
_define(
    "STORAGE", "str", "mem",
    "Default KV backend: 'mem' (WAL-backed in-memory) or 'lsm' "
    "(spill-to-disk SSTables) (storage/kv.py).",
)
_define(
    "STREAM_ENCODER", "bool", True,
    "Streaming arena result encoder (query/streamjson.py): response "
    "JSON streams straight from the ragged (flat_uids, offsets) level "
    "buffers into byte buffers, with native block-at-a-time emission "
    "of hex-uid and count-object arrays — byte-identical to the dict "
    "encoder by contract. 0 is the escape hatch back to the "
    "ExecNode->dict->json.dumps path (query/outputjson.py) for A/B "
    "comparison (tests/test_stream_encoder.py) and triage.",
)
_define(
    "TABLET_TRAFFIC", "bool", True,
    "Per-tablet traffic accounting (utils/observe.py TabletTraffic): "
    "every level read and committed mutation records into a sharded "
    "(namespace, predicate) accumulator served at /debug/tablets and "
    "consumed by the traffic-driven rebalancer. Always-on by design "
    "(its cost has not been measured); 0 is the A/B escape hatch.",
)
_define(
    "TRACE", "bool", True,
    "Master tracing switch, read once per root span. 0 = every span "
    "site under an untraced root is one shared no-op (no ids, no ring, "
    "no histograms, nothing allocated) — the baseline of PERF.md's "
    "chip pairs (utils/observe.py).",
)
_define(
    "TRACE_SAMPLE", "float", 1.0,
    "Trace sampling ratio decided at the root span and propagated in "
    "the wire context (W3C traceparent flags). Unsampled spans still "
    "feed the in-process ring, the per-trace buffer, and latency "
    "histograms; only JSONL/OTLP export is skipped. Slow queries are "
    "force-sampled regardless (utils/observe.py).",
)
_define(
    "TRACE_SINK", "str", "",
    "DIRECTORY for per-process span JSONL sinks: each alpha/zero/"
    "coordinator process writes spans-<instance>.jsonl inside it "
    "(utils/observe.init_from_env). Inherited by spawned replicas.",
)
_define(
    "VEC_COALESCE", "bool", True,
    "Coalesce concurrent plain (unfiltered) similar_to tasks from "
    "different in-flight queries into ONE vector search_batch dispatch "
    "through the serving micro-batcher (query/functions.py + serving/"
    "microbatch.py read_similar). Only active when the batcher itself "
    "is on (DGRAPH_TPU_BATCH_WINDOW_US > 0); results are byte-identical "
    "to solo execution by construction (rows are scored independently).",
)
_define(
    "VEC_NLIST", "int", 0,
    "IVF cell count for vector indexes without an explicit constructor "
    "value; 0 = auto (2*sqrt(n), the FAISS rule of thumb) "
    "(models/vector.py).",
)
_define(
    "VEC_NPROBE", "int", 0,
    "IVF cells probed per vector search for indexes without an explicit "
    "constructor value; 0 = auto (nlist/128, floor 8, on the quantized "
    "engine — top-2 cell multi-assignment already doubles coverage and "
    "serve cost scales ~linearly with the probed pool — and nlist/32, "
    "floor 8, on the jitted float path) (models/vector.py).",
)
_define(
    "VEC_QUANT", "bool", True,
    "Scalar-quantized vector engine: corpus stored as per-row int8 with "
    "scale/offset sidecars, scored by the native qint8 kernels "
    "(codec.cpp vec_qi8_topk*) with a float32 rerank of the surviving "
    "pool (models/vector.py). Applies on CPU-backend hosts above the "
    "small-corpus cutoff; 0 is the A/B escape hatch back to the jitted "
    "float32 paths (tests/test_vector_quant.py).",
)
_define(
    "VEC_REBUILD_IMBALANCE", "float", 4.0,
    "Deferred-repartition trigger for the incremental quantized IVF: "
    "repartition when the max/avg cell ratio GROWS past this multiple "
    "of its post-build baseline (mutation skew — centroids retrained "
    "on a sample, since the old ones would reproduce the same hot "
    "cells), or when tombstoned entries exceed a quarter of the live "
    "corpus (cells reassigned, centroids kept). Mutations themselves "
    "never trigger inline work — inserts append to their nearest "
    "cells, removes tombstone in place (models/vector.py).",
)
_define(
    "VEC_RERANK", "int", 4,
    "Float32 rerank pool as a multiple of k for quantized vector "
    "searches: the qint8 scan keeps rerank*k candidates, which are "
    "re-scored exactly against the float corpus so quantization error "
    "cannot reorder the final top-k (models/vector.py).",
)
_define(
    "VEC_THREADS", "int", 0,
    "Worker threads for the threaded native quantized-vector kernels "
    "(batched candidate-list scan vec_qi8_topk_lists, corpus "
    "quantization vec_qi8_quantize, and the int8 top-2 cell "
    "assignment); 0 = auto, one per core (models/vector.py).",
)
_define(
    "WIRE_COMPRESS", "bool", False,
    "zlib-compress bulk wire blobs; default OFF because zlib-1 is "
    "slower than LAN/ICI-class links — enable for DCN-class links "
    "(conn/frame.py has the host measurement).",
)


# ---------------------------------------------------------------------------
# accessors
# ---------------------------------------------------------------------------


def knob(name: str) -> Knob:
    return REGISTRY[name]


def get_raw(name: str) -> Optional[str]:
    """The raw env string for a registered knob, or None when unset."""
    return os.environ.get(REGISTRY[name].env)


def _env_reader():
    """Fast live env lookup: os.environ.get pays Mapping dispatch plus
    key encode on every call, which adds up on knobs polled per commit
    or per query (the write hot path reads ~10 knobs per txn). The
    underlying os.environ._data dict sees every write made through
    os.environ (set_env, monkeypatch.setenv, direct assignment), so a
    plain dict.get against it keeps read-live-per-call semantics.
    Falls back to os.environ.get when _data is missing or keyed
    differently (non-CPython, Windows)."""
    data = getattr(os.environ, "_data", None)
    if isinstance(data, dict):
        probe = PREFIX + "__PROBE__"
        os.environ[probe] = "1"
        try:
            pb = probe.encode()
            if pb in data:
                dget = data.get

                def read(env: str):
                    raw = dget(env.encode())
                    return raw if raw is None else raw.decode()

                return read
            if probe in data:
                return data.get
        finally:
            del os.environ[probe]
    return os.environ.get


_env_read = _env_reader()
# per-knob (raw, parsed) memo: env reads stay live; only the parse of
# an unchanged raw string is skipped
_parse_memo: Dict[str, tuple] = {}


def get(name: str) -> Any:
    """Parsed value of a registered knob; the declared default when the
    variable is unset or malformed. Reads the environment live on every
    call (tests flip env vars mid-process and expect immediate effect)."""
    k = REGISTRY[name]
    raw = _env_read(k.env)
    if raw is None:
        return k.default
    memo = _parse_memo.get(name)
    if memo is not None and memo[0] == raw:
        return memo[1]
    try:
        val = k.parse(raw)
    except ValueError:
        val = k.default
    _parse_memo[name] = (raw, val)
    return val


def set_env(name: str, value: Any) -> None:
    """Write a knob into the process environment (inherited by spawned
    replicas) — the sanctioned alternative to a raw os.environ write."""
    k = REGISTRY[name]
    if k.type == "bool":
        raw = "1" if value else "0"
    else:
        raw = str(value)
    os.environ[k.env] = raw


def unset_env(name: str) -> None:
    os.environ.pop(REGISTRY[name].env, None)


def is_set(name: str) -> bool:
    return REGISTRY[name].env in os.environ


def resolved() -> Dict[str, Any]:
    """{knob: {env, value, set}} for every registered knob — the
    effective configuration as the process sees it right now. Served at
    /debug/config and captured into debug bundles, so "what was this
    knob during the incident" is answerable from recorded evidence."""
    return {
        name: {
            "env": REGISTRY[name].env,
            "value": get(name),
            "set": is_set(name),
        }
        for name in sorted(REGISTRY)
    }


# ---------------------------------------------------------------------------
# documentation
# ---------------------------------------------------------------------------


def _default_repr(k: Knob) -> str:
    if k.default is None:
        return "_(unset)_"
    if k.type == "bool":
        return "`1`" if k.default else "`0`"
    if k.type == "str":
        return f"`{k.default}`" if k.default else "_(empty)_"
    if k.type == "int" and k.default >= 1 << 16:
        # big byte/size constants read better as shifted forms
        v = int(k.default)
        if v and (v & (v - 1)) == 0:
            return f"`{v}` (1<<{v.bit_length() - 1})"
    return f"`{k.default}`"


def reference_table() -> str:
    """The CONFIG.md body: one Markdown table row per registered knob."""
    lines = [
        "# CONFIG — `DGRAPH_TPU_*` environment reference",
        "",
        "Generated from `dgraph_tpu/x/config.py` "
        "(`python -m dgraph_tpu.cli config-ref`); a tier-1 test asserts "
        "this file matches the registry. Booleans accept "
        "`1/true/yes/on` and `0/false/no/off`; malformed values fall "
        "back to the default instead of crashing.",
        "",
        "| Variable | Type | Default | Description |",
        "|---|---|---|---|",
    ]
    for name in sorted(REGISTRY):
        k = REGISTRY[name]
        doc = " ".join(k.doc.split())
        lines.append(
            f"| `{k.env}` | {k.type} | {_default_repr(k)} | {doc} |"
        )
    lines.append("")
    return "\n".join(lines)
