"""chipbench: one cell of BENCHMARK.json, measured on the served path.

  python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process that holds the chip runs the alpha (`chipbench/alpha.py`);
the closed-loop clients run in a child process (`chipbench/client.py`).
Set-up (data, store, index, upload, compile or cache load, warm-up)
ends when the window's first request is sent. After the window closes
the device's peak memory is read, the alpha is stopped, and the answers
the window produced are compared with the plain reference. A mix whose
query kinds write is judged against the history of its commits
(`chipbench/history.py`), on a copy of the kept store (`install`).

Everything that belongs to one configuration, mix, query kind, layer
metric or planted fault is a file of its own, found by the name in
BENCHMARK.json or in the file that names it: configs/<config>.json,
data/<maker>.py, mixes/<traffic>.json, queries/<kind>.py,
layer_metrics/<metric>.py, faults/<fault>.py. A key of a configuration's
or a mix's file that nothing here reads (a mix's `fault`, which the
tests read) is passed over.

Without a TPU it exits non-zero and prints no result. `--rehearsal` runs
the same code at the configuration's tiny `rehearsal` sizes, under its
`rehearsal_env` (what the CPU needs to take the jitted paths; a variable
the caller has set stands), on whatever platform jax has; its output
says so and is never a result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse
import gc
import hashlib
import importlib
import json
import os
import pickle
import re
import shutil
import subprocess
import sys
import tempfile

import numpy as np

from chipbench import client, history

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
KEEP_STORES = 16  # over the seeds of a check's two sets, so that none is evicted
RUN_COPY = "run-copy"  # a writing run's store, inside the kept store's directory
QUIET_ROUNDS, CAP_ROUNDS = 2, 12  # warm-up: rounds that compile nothing, cap


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no cell {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    mix = load_json(os.path.join(HERE, "mixes", cell["traffic"] + ".json"))
    return cell, config, mix


def load_cell(args):
    """(BENCHMARK.json, cell, configuration, mix) of `args.workload`;
    under `--rehearsal` the configuration is the rehearsed one."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, mix = find_cell(bench, args.workload)
    return bench, cell, (rehearsed(config) if args.rehearsal
                         else config), mix


def rehearsed(config: dict) -> dict:
    """The configuration at its rehearsal sizes, with its rehearsal
    environment put into this process's (the program reads its knobs
    live, and the load generator inherits them)."""
    for name, value in config.get("rehearsal_env", {}).items():
        os.environ.setdefault(name, value)
    return dict(config, sizes=dict(config["sizes"], **config["rehearsal"]))


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def store_dir(config: dict, maker, seed: int) -> str:
    """chipbench/.store/<config>-<seed>-<hash of the maker's source and
    the sizes>: kept between the runs of a cell, as the compile cache
    is; the oldest beyond KEEP_STORES are removed."""
    with open(maker.__file__, "rb") as f:
        h = hashlib.sha256(f.read())
    h.update(json.dumps([config["sizes"], config.get("assumed")],
                        sort_keys=True).encode())
    base = os.path.join(HERE, ".store")
    os.makedirs(base, exist_ok=True)
    mine = f"{config['name']}-{seed}-{h.hexdigest()[:12]}"
    old = sorted((d for d in os.listdir(base) if d != mine),
                 key=lambda d: os.path.getmtime(os.path.join(base, d)))
    for d in old[: max(0, len(old) - (KEEP_STORES - 1))]:
        shutil.rmtree(os.path.join(base, d), ignore_errors=True)
    return os.path.join(base, mine)


def copy_store(kept: str, dst: str) -> None:
    """`kept` as it stands, at `dst`: the LSM's tables (`*.tbl`, written
    once under another name and renamed into place, read through a
    read-only map, removed whole) as hard links, every other file (the
    WAL, which is appended to in place, the manifest, the maker's
    markers) as a copy. What a run writes lands in new files of the copy
    or in its own WAL, and the kept store's bytes stay as they were
    (`chipbench/tests` pins that)."""

    def put(src: str, to: str):
        if src.endswith(".tbl"):
            try:
                return os.link(src, to)
            except OSError:  # another file system: copy it
                pass
        return shutil.copy2(src, to)

    shutil.rmtree(dst, ignore_errors=True)  # left by a run that was cut
    shutil.copytree(kept, dst, copy_function=put,
                    ignore=lambda d, names: [RUN_COPY] if d == kept else [])


def install(maker, config: dict, seed: int, alpha, writes: bool):
    """The maker's `install` on this seed's kept store: (model, what it
    reports, the directory to remove after the run or None). A mix that
    writes never opens the kept store: where the maker keeps stores
    (`kept`), it opens a copy taken before the alpha opens it (built
    first, as ever, where there is no kept store yet), and `copy_s`
    says what the copy cost; no later run of the seed meets its writes."""
    kept = store_dir(config, maker, seed)
    if not (writes and hasattr(maker, "kept")):
        return (*maker.install(config, seed, alpha, kept), None)
    built = None
    if not maker.kept(kept):
        built = maker.install(config, seed, alpha, kept)[1]
        alpha.close()
    copy = os.path.join(kept, RUN_COPY)
    t0 = time.perf_counter()
    copy_store(kept, copy)
    copy_s = time.perf_counter() - t0
    model, info = maker.install(config, seed, alpha, copy)
    info = dict(info, copy_s=copy_s)
    if built is not None:
        info["built"] = built
    return model, info, copy


class Child:
    """The load generator's process and its line protocol."""

    def __init__(self, spec: dict):
        path = os.pathsep.join(
            p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "chipbench.client"], cwd=ROOT, env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.ask(spec)

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def recv(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError("the load generator died")
        return json.loads(line)

    def ask(self, obj: dict) -> dict:
        self.send(obj)
        return self.recv()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send({"cmd": "stop"})
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()


def warm_up(child: Child, clock, mix: dict) -> dict:
    """Run the mix itself, one request per client at a time, until
    QUIET_ROUNDS rounds in a row compiled nothing, under CAP_ROUNDS. Before
    that, where the query kinds know shape classes, send a warm-up
    request of every class the window's planned requests have: which
    pow2 buckets a request reaches depends on the roots drawn, and a
    rare bucket would otherwise compile inside the window."""
    quiet = rounds = 0
    first_s = None
    covered = None
    while quiet < QUIET_ROUNDS and rounds < CAP_ROUNDS:
        before = clock.compiles
        r = child.ask({"cmd": "warm"})
        if r["errors"]:
            raise RuntimeError(f"warm-up request failed: {r['errors'][0]}")
        first_s = r["seconds"] if first_s is None else first_s
        if covered is None:
            covered = child.ask({"cmd": "cover"})
            if covered["errors"]:
                raise RuntimeError(
                    f"warm-up request failed: {covered['errors'][0]}")
        rounds += 1
        quiet = quiet + 1 if clock.compiles == before else 0
    return {"rounds": rounds, "quiet": quiet, "first_round_s": first_s,
            "cover": {k: v for k, v in covered.items() if k != "errors"}}


class GcClock:
    """The collector's passes in this process, which hosts the handler
    threads, while `on`: per generation, how many and their seconds. A
    pass holds the interpreter's lock, so this is a witness for a slow
    window; no metric reads it."""

    def __init__(self):
        self.on = False
        self.count, self.seconds = [0, 0, 0], [0.0, 0.0, 0.0]
        self._t = 0.0
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.count[info["generation"]] += 1
            self.seconds[info["generation"]] += time.perf_counter() - self._t

    def close(self) -> dict:
        gc.callbacks.remove(self)
        return {"count": self.count,
                "seconds": [round(s, 4) for s in self.seconds]}


def answered_by_bin(recs: list, t0: float, seconds: float,
                    width: float = 5.0) -> list:
    """Answers per `width` seconds of the window, for whoever reads a
    far-off run: a stall shows as one low bin, a slow process as all."""
    done = [r["done"] - t0 for r in recs if r["error"] is None]
    edges = np.arange(0.0, seconds + width / 2, width)
    return np.histogram(done, edges)[0].tolist()


def percentile(values, p: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), p))


def end_to_end(name: str, recs: list, t0: float, seconds: float,
               setup_s: float):
    """One end-to-end metric over ALL requests of the window."""
    if name == "setup_s":
        return setup_s
    if name == "qps":
        done = sum(1 for r in recs
                   if r["error"] is None and r["done"] <= t0 + seconds)
        return done / seconds
    m = re.fullmatch(r"latency_p(\d+)_ms", name)
    if m and recs:
        return percentile([(r["done"] - r["sent"]) * 1e3 for r in recs],
                          float(m.group(1)))
    return None


def sample_records(recs: list, mix: dict, seed: int) -> list:
    """The answers compared (reads: a write is judged by the reads that
    see it): all of them, or a sample drawn from the seed with the
    slowest request in it."""
    ok = [r for r in recs if r["error"] is None]
    n = mix.get("compare_sample", 0)
    if not n or len(ok) <= n:
        return ok
    slowest = max(range(len(ok)), key=lambda i: ok[i]["done"] - ok[i]["sent"])
    pick = set(np.random.default_rng([seed, 77]).choice(
        len(ok), n, replace=False).tolist()) | {slowest}
    return [ok[i] for i in sorted(pick)]


AGG = {"sum": np.sum, "mean": np.mean, "max": np.max, "min": np.min}
OPS = {"<=": lambda v, lim: v <= lim, ">=": lambda v, lim: v >= lim}


def kind_of(entry: dict):
    return importlib.import_module(f"chipbench.queries.{entry['kind']}")


def numbers_of(mix: dict, model, recs: list, captured=None,
               answers_of=None) -> dict:
    """{name: [one number per answer or tapped probe]} from every query
    kind's `check`. `captured` is what the data maker tapped from the
    timed programs, if it taps any. `answers_of(kind, params, keys,
    answers)` -> (answers, captured) replaces what the program produced
    (the control, a planted fault)."""
    numbers: dict = {}
    for ki, k in enumerate(mix["kinds"]):
        kind = kind_of(k)
        mine = [r for r in recs if r["kind"] == ki]
        if not mine:
            continue
        keys = [r["key"] for r in mine]
        answers = [r["answer"] for r in mine]
        if answers_of is not None:
            answers, captured = answers_of(kind, k["params"], keys, answers)
        for name, vals in kind.check(model, k["params"], keys, answers,
                                     captured).items():
            numbers.setdefault(name, []).extend(vals)
    return numbers


def judge(config: dict, numbers: dict, failed_requests: int) -> dict:
    """{name: {"value", "op", "limit", "ok"}} for every number the
    configuration's file gives a limit."""
    out = {}
    for name, rule in config["checks"].items():
        vals = numbers.get(name, [])
        value = float(AGG[rule["agg"]](vals)) if vals else 0.0
        out[name] = {"value": value, "op": rule["op"], "limit": rule["limit"],
                     "ok": bool(OPS[rule["op"]](value, rule["limit"]))}
    out["failed_requests"] = {"value": failed_requests, "op": "<=",
                              "limit": 0, "ok": failed_requests == 0}
    return out


def traced(child: Child, seconds: float, mix: dict, out_path: str):
    """Send the window and trace a few seconds of its steady part."""
    import jax

    lead = min(2.0, seconds / 4)
    span = max(0.5, min(mix.get("trace_seconds", 5), seconds - lead - 0.5))
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    child.send({"cmd": "run", "seconds": seconds, "out": out_path})
    time.sleep(lead)
    t_a = time.perf_counter()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    time.sleep(span)
    t_b = time.perf_counter()
    jax.profiler.stop_trace()
    reply = child.recv()
    return reply, {"dir": tdir, "start": t_a, "stop": t_b}


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever platform jax has; never a "
                    "result")
    return ap


def main(argv=None) -> int:
    print(json.dumps(run(parser().parse_args(argv))))
    return 0


def run(args, after=None) -> dict:
    """One run of one cell. `after(state)` (the control) is called with
    what the comparison had in hand — model, config, mix, sample and the
    program's numbers — and what it returns goes into the result under
    "after"."""
    bench, cell, config, mix = load_cell(args)

    # the device first, before any data is built. dgraph_tpu before jax:
    # the package places the persistent compile cache in the checkout
    # (<checkout>/.jax_cache) unless JAX_COMPILATION_CACHE_DIR says where
    import dgraph_tpu  # noqa: F401
    import jax

    from chipbench import alpha as alpha_mod
    from chipbench import span_reduce, trace_reduce

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearsal:
        if device["platform"] != "tpu" or len(devs) < cell["chips"]:
            raise SystemExit(f"chipbench: {args.workload} needs "
                             f"{cell['chips']} TPU chip(s); jax reports "
                             f"{device}")
        pinned = alpha_mod.pinned_knobs()
        if pinned:
            raise SystemExit(f"chipbench: runs at default knobs, but "
                             f"{pinned} are set")
    say(f"{'REHEARSAL, never a result: ' if args.rehearsal else ''}"
        f"{args.workload} seed {args.seed} on {device}")

    maker = importlib.import_module(f"chipbench.data.{config['data']}")
    kinds = [kind_of(k) for k in mix["kinds"]]
    writes = history.writing(kinds)
    clock = alpha_mod.CompileClock()
    gc_clock = GcClock()
    alpha = alpha_mod.Alpha()
    fetches = alpha.fetches
    child = copy = None
    tmp = tempfile.mkdtemp(prefix="chipbench_")
    try:
        model, install_info, copy = install(maker, config, args.seed, alpha,
                                            writes)
        say(f"installed: {install_info}")
        child = Child({"url": alpha.serve(), "config": config, "mix": mix,
                       "seed": args.seed})
        warm = warm_up(child, clock, mix)
        say(f"warm-up: {warm}; compiles so far {clock.snap()}")

        out_path = os.path.join(tmp, "records.pkl")
        c0, f0 = clock.snap(), fetches.snap()
        if hasattr(maker, "window_opens"):
            maker.window_opens(model)
        gc.collect()
        cpu0 = time.process_time()
        setup_s = time.perf_counter() - T0
        trace = None
        gc_clock.on = True
        if args.trace:
            reply, trace = traced(child, args.seconds, mix, out_path)
        else:
            reply = child.ask({"cmd": "run", "seconds": args.seconds,
                               "out": out_path})
        gc_clock.on = False
        c1, f1 = clock.snap(), fetches.snap()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        alpha_cpu_s = time.process_time() - cpu0
        say(f"window closed: {reply}; hbm {alpha_mod.hbm(devs[0])}; the "
            f"alpha's process used {alpha_cpu_s:.1f} cpu-seconds")
        captured = (maker.captured(model) if hasattr(maker, "captured")
                    else None)
        describe = (maker.describe(alpha, model)
                    if hasattr(maker, "describe") else {})
        with open(out_path, "rb") as f:
            got = pickle.load(f)
    finally:
        if child is not None:
            child.close()
        alpha.close()
        shutil.rmtree(tmp, ignore_errors=True)
        if copy is not None:
            shutil.rmtree(copy, ignore_errors=True)
        gc_window = gc_clock.close()
    gc.collect()

    t0, recs = got["t0"], got["records"]
    failed_requests = sum(1 for r in recs if r["error"] is not None)
    for r in recs:
        if r["error"] is not None:
            say(f"failed request: {r['error']}")
            break
    device["memory_peak_bytes"] = int(peak)
    # a witness of a stalled host, for whoever reads a far-off run
    ends = np.sort([t0] + [r["done"] for r in recs])
    longest_gap_s = float(np.max(np.diff(ends))) if len(recs) else 0.0
    by_bin = answered_by_bin(recs, t0, args.seconds)
    say(f"longest time with no answer: {longest_gap_s:.3f}s; answers by 5 s "
        f"{by_bin}; the collector in the window {gc_window}")
    metrics = {}
    ctx = None
    if args.trace:
        planes = span_reduce.read_planes(trace["dir"])
        reduced = trace_reduce.reduce_planes(span_reduce.bare(planes))
        by_span = span_reduce.reduce_planes(planes)["by_span"]
        del planes
        say(f"trace: busy {reduced['busy_s']:.4f}s on "
            f"{reduced['device_planes']} device plane(s); planes "
            f"{[p for p in reduced['planes'] if 'device' in p[0]]}")
        shutil.rmtree(trace["dir"], ignore_errors=True)
        window_s = trace["stop"] - trace["start"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = window_s
        ctx = {
            "trace": dict(reduced, window_s=window_s),
            "traced_requests": sum(
                1 for r in recs if r["error"] is None
                and trace["start"] <= r["done"] <= trace["stop"]),
            "requests": sum(1 for r in recs if r["error"] is None),
            "fetches": alpha_mod.delta(f0, f1),
            "compiles_in_window": c1["compiles"] - c0["compiles"],
            "install": install_info, "warm": warm, "describe": describe,
            "config": config, "device_kind": device["kind"],
            "peaks": load_json(os.path.join(HERE, "peaks.json")),
        }
        for m in metrics_of(bench, "per_layer", args.workload):
            reader = importlib.import_module(
                f"chipbench.layer_metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "end_to_end", args.workload):
            value = end_to_end(m["name"], recs, t0, args.seconds, setup_s)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the comparison that decides `correct`: after the window has closed,
    # the peak has been read and the alpha's state is freed
    t_ref = time.perf_counter()
    past = None
    if writes:
        # every committed write of the run, in commit order
        past = sorted(got["warm_writes"] + client.committed(recs),
                      key=lambda r: r["commit_ts"])
        wrote = {i for i, k in enumerate(kinds) if getattr(k, "WRITES", False)}
        sample = sample_records([r for r in recs if r["kind"] not in wrote],
                                mix, args.seed)
        if captured:
            raise ValueError("a writing mix takes no tapped programs")
        numbers = history.numbers(mix, kinds, model, sample, past)
    else:
        sample = sample_records(recs, mix, args.seed)
        if captured and mix.get("compare_sample"):
            pick = np.random.default_rng([args.seed, 78]).permutation(
                len(captured))[: mix["compare_sample"]]
            captured = [captured[i] for i in sorted(pick)]
        numbers = numbers_of(mix, model, sample, captured)
    checks = judge(config, numbers, failed_requests)
    correct = all(c["ok"] for c in checks.values())
    compare_s = time.perf_counter() - t_ref
    say(f"compared {len(sample)} of {len(recs)} answers"
        + (f" against {len(past)} committed writes (retried "
           f"{sum(w['retries'] for w in past)} times)" if writes else "")
        + f" in {compare_s:.1f}s")

    result = {"correct": correct, "attempted": len(recs),
              "failed": failed_requests + int(
                  checks.get("wrong_answers", {"value": 0})["value"]),
              "metrics": metrics, "device": device}
    if ctx is not None:
        # the idle gaps by the span the launching thread was in
        # (`span_reduce`), largest first: the only trace the ledger keeps
        result["breakdown"] = {
            "device_ops": ctx["trace"]["top_ops"][:10],
            "idle_gaps": [[n, s] for n, s in by_span.items()][:10],
        }
    result["rehearsal"] = bool(args.rehearsal)
    result["compare_s"] = compare_s
    result["setup"] = {"install": install_info, "warm": warm,
                       "compiles": c0, "compiles_in_window":
                       c1["compiles"] - c0["compiles"],
                       "longest_gap_s": longest_gap_s,
                       "alpha_cpu_s": alpha_cpu_s,
                       "answered_by_5s": by_bin, "gc_in_window": gc_window}
    if after is not None:
        result["after"] = after({"model": model, "config": config,
                                 "mix": mix, "kinds": kinds,
                                 "sample": sample, "history": past,
                                 "numbers": numbers, "captured": captured,
                                 "seed": args.seed})
    result["checks"] = {n: [c["value"], c["op"], c["limit"]]
                        for n, c in checks.items()}
    for n, c in checks.items():
        print(f"check {n}: {c['value']!r} {c['op']} {c['limit']!r} -> "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
    return result


if __name__ == "__main__":
    sys.exit(main())
