"""Headline benchmark: batched sorted-UID intersect on the device.

Mirrors the reference's flagship checked-in number — IntersectCompressedWithBin
10-vs-1M at ~2.43us/op on CPU (/root/reference/algo/benchmarks:45). We run
the same shape as a *batch*: 256 independent 10-vs-1M intersections in one
vmapped dispatch (the way the query engine issues them), and report the
per-op amortized latency.

Also reports the compressed-domain path (ops/packed_setops.py — the
direct analog of IntersectCompressedWithBin, which never fully decodes):

  intersect_packed_10v1M_batch256  ns/op for 256 block-skip intersects
  decode_bytes_per_query           decoded vs full-decode bytes across the
                                   selectivity ratio ladder, both operands
                                   compressed; every rung reports which
                                   block kernels ran (bitmap/probe/gallop
                                   — the adaptive set-representation
                                   engine keeps even the dense rungs at
                                   zero decode)

Prints one JSON line per metric:
  {"metric": ..., "value": N, "unit": "ns/op", "vs_baseline": N}
vs_baseline > 1.0 means faster than the reference's 2430 ns/op.
The packed metrics are also stamped into BENCH_PACKED.json via
benchmarks/stamp.guarded_write (a CPU run cannot overwrite a TPU
capture).
"""

import json
import sys
import time

import numpy as np

REF_NS_PER_OP = 2430.0  # algo/benchmarks:45 IntersectCompressedWithBin/ratio=100000
BATCH = 256
SMALL, BIG = 10, 1_000_000
PAD_SMALL = 16
PAD_BIG = 1 << 20


def _build_fanout_graph(fanout=100, pool=200_000):
    """The 3-level 1 -> f -> f^2 -> f^3 traversal graph (~1.01M edges at
    f=100) shared by the fan-out and observability benchmarks. Returns
    (server, query, edges, load_seconds)."""
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    f = fanout
    rng = np.random.default_rng(7)
    l1 = [0x100 + i for i in range(f)]
    l2 = [0x10000 + i for i in range(f * f)]
    base3 = 0x1000000
    lines = [f"<0x1> <child> <{hex(v)}> ." for v in l1]
    for i, v in enumerate(l2):
        lines.append(f"<{hex(l1[i // f])}> <child> <{hex(v)}> .")
    tgts = rng.integers(base3, base3 + pool, size=len(l2) * f)
    for i, v in enumerate(l2):
        hv = hex(v)
        for t in tgts[i * f : (i + 1) * f]:
            lines.append(f"<{hv}> <child> <{hex(int(t))}> .")
    edges = len(lines)

    s = Server()
    s.alter("child: [uid] .")
    t0 = time.perf_counter()
    ParallelBulkLoader(s).load_text("\n".join(lines))
    load_s = time.perf_counter() - t0
    print(f"fanout graph: {edges} edges loaded in {load_s:.1f}s",
          file=sys.stderr)
    q = "{ q(func: uid(0x1)) { child { child { c: count(child) } } } }"
    return s, q, edges, load_s


def _bench_fanout(platform, fanout=100, pool=200_000):
    """Level-batched fan-out headline (BENCH_FANOUT.json):

      fanout_3level_1M        3-level traversal latency over ~1.01M edges
                              (1 -> 100 -> 10k -> 1M), batched level tasks
                              vs the per-uid baseline
                              (DGRAPH_TPU_LEVEL_BATCH=0), both warm
      level_batch_read_calls  cache round-trips per query in each mode —
                              the batched executor issues ONE uids_many
                              per (predicate, level) instead of one
                              uids_tok per parent uid
    """
    import os

    from benchmarks import stamp
    from dgraph_tpu.posting.lists import READ_COUNTERS

    f = fanout
    s, q, edges, load_s = _build_fanout_graph(fanout, pool)

    def run_mode(batch: bool):
        os.environ["DGRAPH_TPU_LEVEL_BATCH"] = "1" if batch else "0"
        s.query(q)  # warm the decoded-list caches
        p0 = READ_COUNTERS.point_reads
        b0 = READ_COUNTERS.batch_reads
        best = float("inf")
        reps = 3
        for _ in range(reps):
            t0 = time.perf_counter()
            out = s.query(q)
            best = min(best, time.perf_counter() - t0)
        trips = (
            (READ_COUNTERS.point_reads - p0)
            + (READ_COUNTERS.batch_reads - b0)
        ) / reps
        n2 = sum(
            len(c1.get("child", []))
            for c1 in out["data"]["q"][0]["child"]
        )
        return best * 1e3, trips, n2

    per_uid_ms, per_uid_trips, n2 = run_mode(batch=False)
    batched_ms, batched_trips, n2b = run_mode(batch=True)
    os.environ.pop("DGRAPH_TPU_LEVEL_BATCH", None)
    assert n2 == n2b, (n2, n2b)
    reduction = per_uid_trips / max(1.0, batched_trips)
    print(
        json.dumps(
            {
                "metric": "fanout_3level_1M",
                "value": round(batched_ms, 2),
                "unit": "ms",
                "per_uid_baseline_ms": round(per_uid_ms, 2),
                "speedup_x": round(per_uid_ms / batched_ms, 2),
                "platform": platform,
            }
        )
    )
    print(
        json.dumps(
            {
                "metric": "level_batch_read_calls",
                "value": batched_trips,
                "unit": "round-trips/query",
                "per_uid_baseline": per_uid_trips,
                "reduction_x": round(reduction, 1),
                "platform": platform,
            }
        )
    )
    stamp.guarded_write(
        "BENCH_FANOUT.json",
        {
            "fanout_3level_1M_ms": {
                "batched": round(batched_ms, 2),
                "per_uid_baseline": round(per_uid_ms, 2),
                "speedup_x": round(per_uid_ms / batched_ms, 2),
            },
            "level_batch_read_calls": {
                "batched": batched_trips,
                "per_uid_baseline": per_uid_trips,
                "reduction_x": round(reduction, 1),
            },
            "graph": {
                "edges": edges,
                "levels": 3,
                "fanout": f,
                "l2_parents": f * f,
                "l3_rows": int(n2),
                "load_seconds": round(load_s, 1),
            },
        },
        platform,
    )


def main():
    # dgraph_tpu before jax: the package places the compile cache, and
    # jax reads that variable at import
    from dgraph_tpu.ops import setops
    from dgraph_tpu.x import device

    import jax
    import jax.numpy as jnp

    platform = device.platform()  # raises when no accelerator was found
    print(f"bench device: {jax.devices()[0]}", file=sys.stderr)

    rng = np.random.default_rng(0)
    big = np.unique(
        rng.integers(0, 1 << 31, BIG + BIG // 8, dtype=np.uint64)
    ).astype(np.uint32)[:BIG]
    B = np.full((PAD_BIG,), 0xFFFFFFFF, np.uint32)
    B[:BIG] = big

    A = np.full((BATCH, PAD_SMALL), 0xFFFFFFFF, np.uint32)
    LA = np.zeros((BATCH,), np.int32)
    for i in range(BATCH):
        # half the small lists are drawn from big (hits), half random
        if i % 2 == 0:
            a = np.sort(rng.choice(big, SMALL, replace=False))
        else:
            a = np.unique(rng.integers(0, 1 << 31, SMALL, dtype=np.uint64)).astype(
                np.uint32
            )[:SMALL]
        A[i, : len(a)] = a
        LA[i] = len(a)

    fn = jax.jit(
        jax.vmap(setops.intersect, in_axes=(0, 0, None, None)),
        static_argnums=(),
    )
    Ad, LAd = jnp.asarray(A), jnp.asarray(LA)
    Bd, LBd = jnp.asarray(B), jnp.asarray(np.int32(BIG))

    # warmup/compile
    out = fn(Ad, LAd, Bd, LBd)
    jax.block_until_ready(out)

    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        out = fn(Ad, LAd, Bd, LBd)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)

    # ratio sweep (stderr only): mirrors algo/benchmarks:2-46 ratio ladder —
    # small side fixed at 10, big side 10*ratio, batch 256
    for ratio in (1, 10, 100, 1000, 10000):
        big_n = SMALL * ratio
        pad = max(16, 1 << (big_n - 1).bit_length())
        Bs = np.full((pad,), 0xFFFFFFFF, np.uint32)
        Bs[:big_n] = np.sort(rng.choice(big, big_n, replace=False))
        f2 = jax.jit(jax.vmap(setops.intersect, in_axes=(0, 0, None, None)))
        o = f2(Ad, LAd, jnp.asarray(Bs), jnp.asarray(np.int32(big_n)))
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(5):
            o = f2(Ad, LAd, jnp.asarray(Bs), jnp.asarray(np.int32(big_n)))
            jax.block_until_ready(o)
        dt = (time.perf_counter() - t0) / 5
        print(
            f"sweep ratio={ratio}: {dt/BATCH*1e9:.1f} ns/op "
            f"(batch {BATCH} in {dt*1e3:.3f} ms)",
            file=sys.stderr,
        )

    per_op_ns = (np.median(times) / BATCH) * 1e9
    result = {
        "metric": "intersect_10v1M_batch256",
        "value": round(per_op_ns, 1),
        "unit": "ns/op",
        "vs_baseline": round(REF_NS_PER_OP / per_op_ns, 3),
        "platform": platform,
    }
    print(
        f"platform={platform} median_batch_ms={np.median(times)*1e3:.3f} "
        f"hits={int(np.asarray(out[1]).sum())}",
        file=sys.stderr,
    )
    print(json.dumps(result))
    _bench_packed(rng, big, platform)
    _bench_fanout(platform)
    _bench_obs(platform)
    _bench_chaos(platform)


def _bench_packed(rng, big, platform):
    """Compressed-domain headline: 256 block-skip 10-vs-1M intersects with
    the big side kept packed (the shape IntersectCompressedWithBin times in
    the reference), plus the decoded-bytes ladder across selectivity
    ratios."""
    from benchmarks import stamp
    from dgraph_tpu.codec import uidpack
    from dgraph_tpu.ops import packed_setops

    b64 = big.astype(np.uint64)
    pack = uidpack.encode(b64)
    smalls = []
    for i in range(BATCH):
        if i % 2 == 0:
            a = np.sort(rng.choice(b64, SMALL, replace=False))
        else:
            a = np.unique(
                rng.integers(0, 1 << 31, SMALL, dtype=np.uint64)
            )[:SMALL]
        smalls.append(a)

    # warm (first-touch candidate metadata: block_maxes builds once)
    packed_setops.intersect_packed(smalls[0], pack)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for a in smalls:
            packed_setops.intersect_packed(a, pack)
        times.append(time.perf_counter() - t0)
    per_op_ns = (np.median(times) / BATCH) * 1e9
    print(
        json.dumps(
            {
                "metric": "intersect_packed_10v1M_batch256",
                "value": round(per_op_ns, 1),
                "unit": "ns/op",
                "vs_baseline": round(REF_NS_PER_OP / per_op_ns, 3),
                "platform": platform,
            }
        )
    )

    # decoded-bytes ladder: per-query decode cost across the selectivity
    # ratio ladder, BOTH operands offered compressed (the posting-list vs
    # posting-list shape every traversal sees). The adaptive per-block
    # engine keeps every rung compressed-domain — bitmap AND on dense
    # block pairs, galloping merge on sparse ones, bitmap probes on mixed
    # — so even the dense rungs (ratio 1/100, which used to fall back to
    # an 8-16 MB full decode) materialize nothing. Each rung reports the
    # per-representation kernel counts alongside the byte accounting.
    from dgraph_tpu.query.dispatch import PackedOperand, SetOpDispatcher

    disp = SetOpDispatcher()
    ladder = []
    for ratio in (1, 100, 1000, 100000):
        n_small = max(10, len(b64) // ratio)
        a = np.sort(rng.choice(b64, n_small, replace=False))
        pack_a = uidpack.encode(a)
        packed_setops.reset_counters()
        got = disp.run_pairs(
            "intersect", [(PackedOperand(pack_a), PackedOperand(pack))]
        )[0]
        c = packed_setops.counters()
        full = (pack.num_uids + pack_a.num_uids) * 8
        decoded = c["decoded_bytes"] if c["packed_ops"] else full
        ladder.append(
            {
                "ratio": ratio,
                "packed_path": bool(c["packed_ops"]),
                "kernels": {
                    "bitmap": int(c["bitmap_pairs"]),
                    "probe": int(c["probe_pairs"]),
                    "gallop": int(c["gallop_pairs"]),
                },
                "streamed_bytes": int(c["streamed_uids"]) * 8,
                "decoded_bytes_per_query": decoded,
                "full_decode_bytes": full,
                "reduction_x": round(full / max(1, decoded), 1),
                "result_n": int(len(got)),
            }
        )
        print(
            f"packed ladder ratio={ratio}: packed={bool(c['packed_ops'])} "
            f"kernels(b/p/g)={int(c['bitmap_pairs'])}/"
            f"{int(c['probe_pairs'])}/{int(c['gallop_pairs'])} "
            f"decoded={decoded}B streamed={int(c['streamed_uids'])*8}B "
            f"full={full}B reduction={full/max(1,decoded):.1f}x",
            file=sys.stderr,
        )
    headline = ladder[-1]  # the 10-vs-1M (most selective) row
    print(
        json.dumps(
            {
                "metric": "decode_bytes_per_query",
                "value": headline["decoded_bytes_per_query"],
                "unit": "bytes",
                "reduction_x": headline["reduction_x"],
                "ladder": ladder,
                "platform": platform,
            }
        )
    )
    stamp.guarded_write(
        "BENCH_PACKED.json",
        {
            "intersect_packed_10v1M_batch256_ns": round(per_op_ns, 1),
            "decode_bytes_ladder": ladder,
        },
        platform,
    )


def _bench_obs(platform, fanout=100, pool=200_000):
    """Tracing overhead (BENCH_OBS.json): the fanout_3level_1M warm
    query under three modes — tracing OFF (DGRAPH_TPU_TRACE=0),
    enabled-but-UNSAMPLED (the production default posture: context
    propagates, histograms fill, nothing exported), and FULLY SAMPLED
    with every span written to a JSONL sink — plus the sink's raw
    spans/s throughput. The acceptance bar: enabled-unsampled must stay
    within 5% of off, proving instrumentation is off the hot path."""
    import os
    import tempfile

    from benchmarks import stamp
    from dgraph_tpu.utils import observe
    from dgraph_tpu.x import config

    s, q, edges, load_s = _build_fanout_graph(fanout, pool)

    def run_mode(trace: bool, sample: float, sink: str = "", env=None,
                 reps: int = 5):
        config.set_env("TRACE", trace)
        config.set_env("TRACE_SAMPLE", sample)
        for k, v in (env or {}).items():
            config.set_env(k, v)
        observe.TRACER.set_sink(sink or None)
        try:
            s.query(q)  # warm caches under the mode's settings
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                s.query(q)
                best = min(best, time.perf_counter() - t0)
            return best * 1e3
        finally:
            observe.TRACER.set_sink(None)
            config.unset_env("TRACE")
            config.unset_env("TRACE_SAMPLE")
            for k in env or {}:
                config.unset_env(k)

    sink_path = os.path.join(
        tempfile.mkdtemp(prefix="dgraph_obs_bench_"), "spans.jsonl"
    )
    off_ms = run_mode(trace=False, sample=0.0)
    unsampled_ms = run_mode(trace=True, sample=0.0)
    sampled_ms = run_mode(trace=True, sample=1.0, sink=sink_path)
    overhead_pct = (unsampled_ms - off_ms) / off_ms * 100.0

    # always-on accounting A/B: per-tablet traffic + exemplars + query
    # digests + metrics history (all on by default).  The flight-recorder
    # gate requires the always-on arm within 1% of accounting-off,
    # asserted in-capture — interleaved best-of-9 pairs so minute-scale
    # box drift cancels
    from dgraph_tpu.serving.digest import DIGESTS

    observe.TABLETS.clear()
    DIGESTS.reset()
    _obs_off = {"TABLET_TRAFFIC": 0, "EXEMPLARS": 0,
                "DIGEST": 0, "HISTORY": 0}
    _obs_on = {"TABLET_TRAFFIC": 1, "EXEMPLARS": 1,
               "DIGEST": 1, "HISTORY": 1}
    acct_off_ms = float("inf")
    acct_on_ms = float("inf")
    for _ in range(9):
        acct_off_ms = min(acct_off_ms, run_mode(
            trace=True, sample=0.0, env=_obs_off, reps=1,
        ))
        acct_on_ms = min(acct_on_ms, run_mode(
            trace=True, sample=0.0, env=_obs_on, reps=1,
        ))
    assert observe.TABLETS.snapshot(), "accounting arm recorded nothing"
    assert DIGESTS.snapshot(), "digest arm recorded nothing"
    acct_overhead_pct = (acct_on_ms - acct_off_ms) / acct_off_ms * 100.0
    assert acct_overhead_pct <= 1.0, (
        f"always-on accounting (traffic + exemplars + digests + "
        f"history) cost {acct_overhead_pct:.2f}% on fanout_3level_1M "
        f"(on {acct_on_ms:.2f}ms vs off {acct_off_ms:.2f}ms); "
        f"the flight-recorder gate requires <= 1%"
    )

    # profiler-armed leg, reported separately (sampling is an opt-in,
    # bounded capture — not part of the always-on <=1% contract): the
    # same query timed while a wall-clock capture is actively walking
    # sys._current_frames() at PROFILE_HZ
    import threading as _threading

    from dgraph_tpu.utils.profiler import PROFILER

    prof_base_ms = run_mode(trace=True, sample=0.0, reps=3)
    capture_s = min(5.0, max(0.5, 10 * prof_base_ms / 1e3))
    folded_box = {}
    cap = _threading.Thread(
        target=lambda: folded_box.setdefault(
            "folded", PROFILER.profile(capture_s)
        ),
        daemon=True,
    )
    cap.start()
    prof_armed_ms = run_mode(trace=True, sample=0.0, reps=3)
    cap.join()
    assert folded_box.get("folded"), "profiler capture saw no stacks"
    prof_overhead_pct = (
        (prof_armed_ms - prof_base_ms) / prof_base_ms * 100.0
    )

    # raw JSONL sink throughput: how many spans/s the exporter absorbs
    n_spans = 20_000
    tr = observe.Tracer(capacity=16, sink_path=sink_path + ".tput")
    t0 = time.perf_counter()
    for _ in range(n_spans):
        with tr.span("bench"):
            pass
    sink_spans_per_s = n_spans / (time.perf_counter() - t0)

    for metric, value, extra in (
        (
            "fanout_3level_1M_traced",
            round(unsampled_ms, 2),
            {
                "unit": "ms",
                "tracing_off_ms": round(off_ms, 2),
                "fully_sampled_ms": round(sampled_ms, 2),
                "unsampled_overhead_pct": round(overhead_pct, 2),
            },
        ),
        (
            "fanout_3level_1M_accounting",
            round(acct_on_ms, 2),
            {
                "unit": "ms",
                "accounting_off_ms": round(acct_off_ms, 2),
                "overhead_pct": round(acct_overhead_pct, 2),
                "digest_shapes": len(DIGESTS.snapshot()),
            },
        ),
        (
            "fanout_3level_1M_profiler_armed",
            round(prof_armed_ms, 2),
            {
                "unit": "ms",
                "unarmed_ms": round(prof_base_ms, 2),
                "overhead_pct": round(prof_overhead_pct, 2),
            },
        ),
        (
            "trace_sink_throughput",
            round(sink_spans_per_s),
            {"unit": "spans/s"},
        ),
    ):
        print(
            json.dumps(
                {"metric": metric, "value": value, **extra,
                 "platform": platform}
            )
        )
    stamp.guarded_write(
        "BENCH_OBS.json",
        {
            "fanout_3level_1M_ms": {
                "tracing_off": round(off_ms, 2),
                "enabled_unsampled": round(unsampled_ms, 2),
                "fully_sampled_jsonl": round(sampled_ms, 2),
            },
            "unsampled_overhead_pct": round(overhead_pct, 2),
            "traffic_accounting_ms": {
                "accounting_off": round(acct_off_ms, 2),
                "accounting_on": round(acct_on_ms, 2),
                "overhead_pct": round(acct_overhead_pct, 2),
            },
            "profiler_armed_ms": {
                "unarmed": round(prof_base_ms, 2),
                "armed": round(prof_armed_ms, 2),
                "overhead_pct": round(prof_overhead_pct, 2),
            },
            "jsonl_sink_spans_per_s": round(sink_spans_per_s),
            "graph": {"edges": edges, "load_seconds": round(load_s, 1)},
        },
        platform,
    )


def _build_flat_graph(n=1_000_000):
    """One hub with n uid-pred followers — the result-size ladder for
    the encoder bench (pagination slices the SAME level buffers, so
    every rung measures encoding over identical executor work)."""
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    s = Server()
    s.alter("follow: [uid] .")
    lines = [f"<0x1> <follow> <{hex(0x10 + i)}> ." for i in range(n)]
    t0 = time.perf_counter()
    ParallelBulkLoader(s).load_text("\n".join(lines))
    load_s = time.perf_counter() - t0
    print(f"flat graph: {n} edges loaded in {load_s:.1f}s", file=sys.stderr)
    return s, load_s


def _encode_rung(s, q, reps=3):
    """Best-of-reps (encoding_ns, total_ns, bytes, share) for q through
    the PUBLIC query path with `want='raw'` (the serving surface — no
    dict parse-back in the loop)."""
    s.query(q, want="raw")  # warm decoded-list caches + plan cache
    best = None
    for _ in range(reps):
        res = s.query(q, want="raw")
        lat = res["extensions"]["server_latency"]
        enc = res["extensions"]["profile"]["encode"]
        row = (
            int(lat["encoding_ns"]),
            int(lat["total_ns"]),
            int(enc["bytes"]),
            float(enc.get("share", 0.0)),
        )
        if best is None or row[0] < best[0]:
            best = row
    return best


def _bench_encode(platform, sanity=False):
    """Streaming arena encoder ladder (BENCH_ENCODE.json):

      encode_share_ladder   encoding_ns (from extensions.server_latency)
                            and encode share of total at 1k/100k/1M-uid
                            results, dict encoder
                            (DGRAPH_TPU_STREAM_ENCODER=0) vs streaming
                            arena (=1) over the same warm server — the
                            A/B rides the registered escape hatch, both
                            paths producing the SAME wire bytes

    --encode-sanity: one small rung, assert byte-identity + print the
    numbers, no stamping (the tools/check.sh smoke gate).
    """
    import os

    from benchmarks import stamp

    n_max = 100_000 if sanity else 1_000_000
    rungs = [100_000] if sanity else [1_000, 100_000, 1_000_000]
    s, load_s = _build_flat_graph(n_max)

    ladder = []
    for n in rungs:
        q = "{ q(func: uid(0x1)) { follow(first: %d) { uid } } }" % n
        row = {"uids": n}
        raws = {}
        for flag, key in (("0", "dict"), ("1", "stream")):
            os.environ["DGRAPH_TPU_STREAM_ENCODER"] = flag
            enc_ns, total_ns, nbytes, share = _encode_rung(
                s, q, reps=1 if sanity else 3
            )
            raws[key] = s.query(q, want="raw")["data"].raw
            row[key] = {
                "encoding_ns": enc_ns,
                "total_ns": total_ns,
                "bytes": nbytes,
                "encode_share": round(share, 4),
            }
        os.environ.pop("DGRAPH_TPU_STREAM_ENCODER", None)
        assert raws["dict"] == raws["stream"], (
            f"byte-identity violated at {n} uids"
        )
        row["reduction_x"] = round(
            row["dict"]["encoding_ns"]
            / max(1, row["stream"]["encoding_ns"]),
            2,
        )
        ladder.append(row)
        print(
            json.dumps(
                {
                    "metric": "encoding_ns",
                    "uids": n,
                    "dict": row["dict"]["encoding_ns"],
                    "stream": row["stream"]["encoding_ns"],
                    "reduction_x": row["reduction_x"],
                    "encode_share_dict": row["dict"]["encode_share"],
                    "encode_share_stream": row["stream"]["encode_share"],
                    "platform": platform,
                }
            )
        )
    if sanity:
        print("encode sanity: byte-identity + ladder ok", file=sys.stderr)
        return
    stamp.guarded_write(
        "BENCH_ENCODE.json",
        {
            "encode_share_ladder": ladder,
            "graph": {"edges": n_max, "load_seconds": round(load_s, 1)},
        },
        platform,
    )


def _bench_vector(platform, sanity=False):
    """Quantized vector engine A/B (BENCH_VECTOR.json, ISSUE 9):

      float_brute        the jitted float32 batched scan, forced via the
                         DGRAPH_TPU_VEC_QUANT=0 escape hatch — the exact
                         baseline AND the recall ground truth
      quant_brute        the int8 scan kernels, full corpus (exact after
                         the float32 rerank — recall should be ~1.0)
      quant_ivf          the incremental quantized IVF tier (sampled
                         mini-batch k-means + top-2 cell assignment);
                         reports build seconds vs the r5 255s sync train
      incremental        inserts + removes against the built IVF index:
                         asserts NO rebuild ran and results stay correct

    All tiers run in the SAME process over the SAME corpus (same-run
    A/B). --vector-sanity shrinks the corpus to a ~5s gate that asserts
    exact A/B top-k equality + recall floors, and stamps nothing.
    """
    import gc
    import os

    from benchmarks import stamp
    from dgraph_tpu.models import vector as vecmod
    from dgraph_tpu.models.vector import VectorIndex

    n, d = (20_000, 64) if sanity else (1_000_000, 768)
    k, qb = 10, 64
    nq = 64 if sanity else 256
    if sanity:
        # the quantized engine normally wants >= _QUANT_MIN live rows
        vecmod._QUANT_MIN = 1
    rng = np.random.default_rng(1)
    # mixture-of-gaussians corpus: real embedding sets cluster; pure
    # isotropic gaussian is IVF's pathological worst case (distance
    # concentration) and misrepresents production recall
    n_clusters = 256
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32) * 4.0
    V = (
        centers[rng.integers(0, n_clusters, n)]
        + rng.standard_normal((n, d)).astype(np.float32)
    )
    Qs = (
        centers[rng.integers(0, n_clusters, nq)]
        + rng.standard_normal((nq, d))
    ).astype(np.float32)
    uids = np.arange(1, n + 1, dtype=np.uint64)

    def timed_batches(ix):
        ix.search_batch(Qs[:qb], k)  # warm (compile / quantize view)
        t0 = time.perf_counter()
        rows = [
            ix.search_batch(Qs[i : i + qb], k) for i in range(0, nq, qb)
        ]
        dt = time.perf_counter() - t0
        return np.concatenate(rows, axis=0), nq / dt

    def recall(got, exact):
        hits = sum(
            len(set(map(int, got[i])) & set(map(int, exact[i])))
            for i in range(nq)
        )
        return hits / (nq * k)

    out = {"n_vectors": n, "dim": d, "query_batch": qb, "k": k}

    # -- A: float32 jit brute (escape hatch) — baseline + ground truth
    os.environ["DGRAPH_TPU_VEC_QUANT"] = "0"
    idx = VectorIndex("emb", ivf_threshold=1 << 62)
    idx.bulk_load(uids, V)
    exact, float_qps = timed_batches(idx)
    assert not idx._use_quant()
    idx._device = None
    del idx
    gc.collect()
    out["float_brute_qps"] = round(float_qps, 1)

    # -- B: quantized int8 brute (native kernels + float32 rerank)
    os.environ["DGRAPH_TPU_VEC_QUANT"] = "1"
    idxq = VectorIndex("emb", ivf_threshold=1 << 62)
    idxq.bulk_load(uids, V)
    t0 = time.perf_counter()
    idxq._quant_view()  # quantize the corpus (no IVF at this threshold)
    out["quantize_seconds"] = round(time.perf_counter() - t0, 1)
    assert idxq._use_quant(), "quantized engine must engage for the A/B"
    qgot, quant_qps = timed_batches(idxq)
    out["quant_brute_qps"] = round(quant_qps, 1)
    out["quant_brute_recall_at_10"] = round(recall(qgot, exact), 3)
    del idxq
    gc.collect()

    # -- C: quantized incremental IVF (build + serve)
    idx2 = VectorIndex("emb2", ivf_threshold=1)
    idx2.bulk_load(uids, V)
    t0 = time.perf_counter()
    idx2._quant_view()  # quantize + centroid train + cell assignment
    build_s = time.perf_counter() - t0
    out["ivf_build_seconds"] = round(build_s, 1)
    igot, ivf_qps = timed_batches(idx2)
    out["quant_ivf_qps"] = round(ivf_qps, 1)
    out["quant_ivf_recall_at_10"] = round(recall(igot, exact), 3)

    idx2.search(Qs[0], k)  # warm the single-query path
    t0 = time.perf_counter()
    for q in Qs[:10]:
        idx2.search(q, k)
    out["ivf_latency_ms_single"] = round(
        (time.perf_counter() - t0) / 10 * 1e3, 2
    )

    # -- D: incremental mutations serve correct results, NO rebuild
    builds_before = (idx2.build_count, idx2.repartition_count)
    new_vecs = centers[rng.integers(0, n_clusters, 64)] + rng.standard_normal(
        (64, d)
    ).astype(np.float32)
    t0 = time.perf_counter()
    for j in range(64):
        idx2.insert(n + 1 + j, new_vecs[j])
    for u in rng.choice(uids, 64, replace=False):
        idx2.remove(int(u))
    res = idx2.search_batch(new_vecs[:16], k)
    mut_ms = (time.perf_counter() - t0) * 1e3
    assert (idx2.build_count, idx2.repartition_count) == builds_before, (
        "mutation triggered a rebuild/repartition"
    )
    assert all(int(res[j][0]) == n + 1 + j for j in range(16)), (
        "inserted vectors not served as their own nearest neighbor"
    )
    out["incremental_64ins_64del_plus_16q_ms"] = round(mut_ms, 1)

    best_qps = max(out["quant_brute_qps"], out["quant_ivf_qps"])
    out["speedup_x_vs_float_brute"] = round(best_qps / max(float_qps, 1e-9), 1)
    out["build_speedup_x_vs_r5_sync"] = round(255.0 / max(build_s, 1e-9), 1)
    out["native_kernels"] = __import__(
        "dgraph_tpu.native", fromlist=["NATIVE_AVAILABLE"]
    ).NATIVE_AVAILABLE
    os.environ.pop("DGRAPH_TPU_VEC_QUANT", None)

    for metric in (
        "float_brute_qps", "quant_brute_qps", "quant_ivf_qps",
        "quant_brute_recall_at_10", "quant_ivf_recall_at_10",
        "ivf_build_seconds", "speedup_x_vs_float_brute",
    ):
        print(
            json.dumps(
                {"metric": metric, "value": out[metric],
                 "platform": platform}
            )
        )

    if sanity:
        # exact A/B identity: both brute tiers are exact, so each row's
        # top-k SET must match (ordering of ulp-close neighbors may
        # differ between the XLA matmul and the rerank dot)
        assert np.array_equal(np.sort(qgot, 1), np.sort(exact, 1)), (
            "quant/float brute A/B differ"
        )
        assert out["quant_ivf_recall_at_10"] >= 0.95, out
        print("vector sanity: A/B identity + recall + no-rebuild ok",
              file=sys.stderr)
        return
    stamp.guarded_write("BENCH_VECTOR.json", out, platform)


def _bench_chaos(platform):
    """Retry-storm visibility (BENCH_CHAOS.json): a fixed-seed fault
    schedule (drops + delays + disconnects + lost acks) over an
    in-process RPC pair, with idempotent retries. Stamps wall time and
    the fault/retry/idempotency counters so a regression that turns
    recoverable faults into retry storms — or worse, double-applies —
    shows up as a diff in the artifact."""
    import time as _t

    from benchmarks import stamp
    from dgraph_tpu.conn import faults as _faults
    from dgraph_tpu.conn.faults import FaultPlan
    from dgraph_tpu.conn.retry import Deadline
    from dgraph_tpu.conn.rpc import RpcClient, RpcServer
    from dgraph_tpu.utils.observe import METRICS

    N = 400
    srv = RpcServer().start()
    applied = []
    srv.register("apply", lambda a: applied.append(a["v"]) or {"ok": True})
    keys = (
        "rpc_retries_total", "rpc_giveups_total", "faults_injected_total",
        "fault_drop_total", "fault_delay_total", "fault_disconnect_total",
        "idem_hits_total",
    )
    before = {k: METRICS.value(k) for k in keys}
    _faults.install(
        FaultPlan(
            seed=2024,
            rules=[
                {"point": "send", "action": "drop", "p": 0.06},
                {"point": "send", "action": "delay", "p": 0.10,
                 "delay_ms": 2},
                {"point": "send", "action": "disconnect", "p": 0.04},
                {"point": "resp", "action": "drop", "p": 0.04},
            ],
        )
    )
    try:
        c = RpcClient(srv.addr, timeout=0.1)
        t0 = _t.perf_counter()
        for i in range(N):
            c.call("apply", {"v": i}, timeout=0.1,
                   deadline=Deadline.after(10.0), idem=True)
        wall = _t.perf_counter() - t0
    finally:
        _faults.reset()
        srv.close()
    delta = {k: METRICS.value(k) - before[k] for k in keys}
    lost = N - len(set(applied))
    dupes = len(applied) - len(set(applied))
    result = {
        "metric": "chaos_rpc_400calls",
        "value": round(wall, 3),
        "unit": "s",
        "retries_per_100_calls": round(delta["rpc_retries_total"] / N * 100, 1),
        "faults_injected": delta["faults_injected_total"],
        "idem_hits": delta["idem_hits_total"],
        "lost_applies": lost,
        "double_applies": dupes,
        "platform": platform,
    }
    print(json.dumps(result))
    assert lost == 0 and dupes == 0, (lost, dupes)
    stamp.guarded_write(
        "BENCH_CHAOS.json",
        {
            "chaos_rpc_400calls_s": round(wall, 3),
            "seed": 2024,
            "counters": {k: delta[k] for k in keys},
            "retries_per_100_calls": result["retries_per_100_calls"],
            "lost_applies": lost,
            "double_applies": dupes,
        },
        platform,
    )


def _explain_sanity():
    """The ~5s CI gate for the EXPLAIN surface (tools/check.sh
    --explain-sanity): debug on/off byte-equality over the DQL golden
    smoke subset, schema validation of every captured plan, and one
    rendered-plan snapshot through the CLI renderer."""
    import os as _os

    from dgraph_tpu.api.server import Server
    from dgraph_tpu.cli import render_plan

    here = _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)), "tests", "ref_golden"
    )
    cases = json.load(open(_os.path.join(here, "cases.json")))[::9]
    s = Server()
    s.alter(open(_os.path.join(here, "schema.txt")).read())
    for rdf in ("triples.rdf", "triples_facets.rdf"):
        t = s.new_txn()
        t.mutate_rdf(
            set_rdf=open(_os.path.join(here, rdf)).read(),
            commit_now=True,
        )

    def data_bytes(d):
        raw = getattr(d, "raw", None)
        return (
            bytes(raw)
            if raw is not None
            else json.dumps(d, sort_keys=True).encode()
        )

    checked = planned = 0
    for case in cases:
        q = case["query"]
        try:
            plain = data_bytes(s.query(q, want="raw")["data"])
        except Exception:
            continue  # error queries covered by tests/test_explain.py
        res = s.query(q, want="raw", debug=True)
        assert data_bytes(res["data"]) == plain, case["id"]
        plan = res["extensions"]["plan"]
        assert isinstance(plan["nodes"], list), case["id"]
        checked += 1
        planned += bool(plan["nodes"])
    assert checked >= 30, f"only {checked} smoke cases executed"
    # one rendered-plan snapshot: the renderer's contract lines
    res = s.query(
        "{ q(func: has(name)) { name friend { uid } } }", debug=True
    )
    out = render_plan(res["extensions"]["plan"])
    assert out.startswith("Query plan (wall "), out
    assert "\n  plan cache: " in out and "\n  admission: " in out, out
    assert "friend level=1 [batched]" in out, out
    print(
        json.dumps(
            {
                "explain_sanity": "OK",
                "cases_checked": checked,
                "cases_with_plan_nodes": planned,
            }
        )
    )


def _plan_sanity():
    """The ~5s CI gate for the cost-based planner + result cache
    (tools/check.sh --plan-sanity): planner on/off AND result-cache
    off/miss/hit byte-equality over the DQL golden smoke subset, with
    the decision counters asserted live."""
    import os as _os

    from dgraph_tpu.api.server import Server
    from dgraph_tpu.utils.observe import METRICS

    here = _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)), "tests", "ref_golden"
    )
    cases = json.load(open(_os.path.join(here, "cases.json")))[::9]
    s = Server()
    s.alter(open(_os.path.join(here, "schema.txt")).read())
    for rdf in ("triples.rdf", "triples_facets.rdf"):
        t = s.new_txn()
        t.mutate_rdf(
            set_rdf=open(_os.path.join(here, rdf)).read(),
            commit_now=True,
        )

    def run(q):
        try:
            d = s.query(q, want="raw")["data"]
            raw = getattr(d, "raw", None)
            return (
                bytes(raw)
                if raw is not None
                else json.dumps(d, sort_keys=True).encode()
            )
        except Exception as exc:
            return f"{type(exc).__name__}: {exc}"

    def with_env(q, **env):
        from dgraph_tpu.x import config as _config

        saved = {k: _config.get_raw(k) for k in env}
        for k, v in env.items():
            _config.set_env(k, v)
        try:
            return run(q)
        finally:
            for k, old in saved.items():
                if old is None:
                    _config.unset_env(k)
                else:
                    _config.set_env(k, old)

    r0 = METRICS.value("planner_reorders_total")
    h0 = METRICS.value("result_cache_hit_total")
    checked = 0
    for case in cases:
        q = case["query"]
        base = with_env(q, QUERY_PLANNER=0, RESULT_CACHE_SIZE=0)
        planner_on = with_env(q, QUERY_PLANNER=1, RESULT_CACHE_SIZE=0)
        assert planner_on == base, f"planner changed bytes: {case['id']}"
        first = with_env(q, RESULT_CACHE_SIZE=4096)
        second = with_env(q, RESULT_CACHE_SIZE=4096)  # the HIT
        assert first == base and second == base, (
            f"result cache changed bytes: {case['id']}"
        )
        checked += 1
    assert checked >= 30, f"only {checked} smoke cases executed"
    reorders = METRICS.value("planner_reorders_total") - r0
    hits = METRICS.value("result_cache_hit_total") - h0
    assert reorders > 0, "planner never reordered over the smoke subset"
    assert hits > 0, "result cache never hit over the smoke subset"
    print(
        json.dumps(
            {
                "plan_sanity": "OK",
                "cases_checked": checked,
                "planner_reorders": int(reorders),
                "result_cache_hits": int(hits),
            }
        )
    )


def _obs_sanity():
    """The ~5s CI gate for the flight recorder (tools/check.sh
    --obs-sanity): recorder on/off byte-equality over the DQL golden
    smoke subset, with the digest store and metrics history asserted
    live on the recorder-on arm."""
    import os as _os

    from dgraph_tpu.api.server import Server
    from dgraph_tpu.serving.digest import DIGESTS
    from dgraph_tpu.utils import observe
    from dgraph_tpu.x import config as _config

    here = _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)), "tests", "ref_golden"
    )
    cases = json.load(open(_os.path.join(here, "cases.json")))[::9]
    s = Server()
    s.alter(open(_os.path.join(here, "schema.txt")).read())
    for rdf in ("triples.rdf", "triples_facets.rdf"):
        t = s.new_txn()
        t.mutate_rdf(
            set_rdf=open(_os.path.join(here, rdf)).read(),
            commit_now=True,
        )

    def run(q):
        try:
            d = s.query(q, want="raw")["data"]
            raw = getattr(d, "raw", None)
            return (
                bytes(raw)
                if raw is not None
                else json.dumps(d, sort_keys=True).encode()
            )
        except Exception as exc:
            return f"{type(exc).__name__}: {exc}"

    def with_env(q, **env):
        saved = {k: _config.get_raw(k) for k in env}
        for k, v in env.items():
            _config.set_env(k, v)
        try:
            return run(q)
        finally:
            for k, old in saved.items():
                if old is None:
                    _config.unset_env(k)
                else:
                    _config.set_env(k, old)

    DIGESTS.reset()
    observe.HISTORY.reset()
    checked = 0
    for case in cases:
        q = case["query"]
        off = with_env(q, DIGEST=0, HISTORY=0)
        on = with_env(q, DIGEST=1, HISTORY=1)
        assert on == off, f"flight recorder changed bytes: {case['id']}"
        checked += 1
    assert checked >= 30, f"only {checked} smoke cases executed"
    digests = DIGESTS.snapshot()
    assert digests, "recorder-on arm recorded no digests"
    calls = sum(r["calls"] for r in digests)
    # one history snapshot on demand proves the ring's record path works
    # without waiting out the sampler interval
    saved = _config.get_raw("HISTORY")
    _config.set_env("HISTORY", 1)
    try:
        observe.HISTORY.record_now()
        observe.HISTORY.record_now()
    finally:
        if saved is None:
            _config.unset_env("HISTORY")
        else:
            _config.set_env("HISTORY", saved)
    hist = observe.HISTORY.report(window_s=60.0)
    assert hist["samples"] >= 2, hist
    print(
        json.dumps(
            {
                "obs_sanity": "OK",
                "cases_checked": checked,
                "digest_shapes": len(digests),
                "digest_calls": int(calls),
                "history_samples": hist["samples"],
            }
        )
    )


if __name__ == "__main__":
    # dgraph_tpu before jax (the package places the compile cache)
    from dgraph_tpu.x import device

    if "--explain-sanity" in sys.argv:
        _explain_sanity()
    elif "--plan-sanity" in sys.argv:
        _plan_sanity()
    elif "--obs-sanity" in sys.argv:
        _obs_sanity()
    elif "--write-sanity" in sys.argv:
        # mixed read/write smoke incl. the columnar batch-apply arm
        # check (delegates to the loadgen's gate; host-path only)
        from benchmarks import qps_loadgen

        sys.exit(qps_loadgen.main(["--write-sanity"]))
    elif "--chaos-only" in sys.argv:
        # host-only capture: no device involved in the RPC plane
        _bench_chaos("cpu")
    elif "--fanout-only" in sys.argv:
        # query-engine-only capture
        _bench_fanout(device.platform())
    elif "--encode-only" in sys.argv or "--encode-sanity" in sys.argv:
        # encoder-path capture (BENCH_ENCODE.json); host-path only
        _bench_encode(
            device.platform(),
            sanity="--encode-sanity" in sys.argv,
        )
    elif "--vector-only" in sys.argv or "--vector-sanity" in sys.argv:
        # quantized-vector-engine capture (BENCH_VECTOR.json); host-path
        _bench_vector(
            device.platform(),
            sanity="--vector-sanity" in sys.argv,
        )
    elif "--obs-only" in sys.argv:
        # tracing-overhead capture (BENCH_OBS.json); host-path only
        _bench_obs(device.platform())
    else:
        main()
