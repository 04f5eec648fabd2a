"""The system under test, started the way `dgraph-tpu alpha` starts it:
an engine from `cli._server` on `--storage backend=lsm`, behind
`api/http_server.HTTPServer`, default knobs. Also the three observers
that look at it from outside (copies of chip_smoke.py's): jax's compile
events, the dispatcher's jit fetches, the device's memory.

This is the only file of the benchmark, with the data makers' `install`,
that imports the program."""

from __future__ import annotations

import argparse
import collections
import threading

PINNED_KNOBS = ("FORCE_DEVICE", "DEVICE_MIN_TOTAL", "BATCH_WINDOW_US")


class CompileClock:
    """XLA compilations and persistent-cache traffic, from jax's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snap(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class JitFetches:
    """Every fetch of a jitted function from the dispatcher's or the
    vector index's jit cache is followed by one execution on the device,
    so the fetches between two marks are the device dispatches of the
    requests in between. Patched from outside; `PERF.md` asks the
    `tracing` issue for a counter in the program to take its place.
    It is the one wrapper of those getters: whoever else has to see a
    fetch (the vector maker's tap of the probe's own distances) adds a
    `watch(label, key, fn) -> fn` to `watchers`."""

    def __init__(self):
        from dgraph_tpu.models import vector
        from dgraph_tpu.query.dispatch import DISPATCHER

        self.counts = collections.Counter()
        self.watchers = []
        self._lock = threading.Lock()
        for name, suffix in (("_get_jitted", ""),
                             ("_get_jitted_shared", "#shared"),
                             ("_get_jitted_chain", "#chain")):
            setattr(DISPATCHER, name,
                    self._wrap(getattr(DISPATCHER, name), "setop:%s" + suffix))
        for name in ("_jit_brute", "_jit_brute_batch", "_jit_ivf",
                     "_jit_ivf_batch"):
            setattr(vector, name,
                    self._wrap(getattr(vector, name), "vector:" + name[5:]))

    def _wrap(self, orig, label: str):
        def fetch(*a):
            name = label % a[0] if "%s" in label else label
            with self._lock:
                self.counts[name] += 1
            fn = orig(*a)
            for watch in self.watchers:
                fn = watch(name, a, fn)
            return fn

        return fetch

    def snap(self) -> dict:
        with self._lock:
            return dict(self.counts)


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0) for k in b if b[k] != a.get(k, 0)}


def hbm(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def pinned_knobs() -> list:
    from dgraph_tpu.x import config

    return [k for k in PINNED_KNOBS if config.is_set(k)]


class Alpha:
    """One engine and its HTTP front. `close` stops everything started."""

    def __init__(self):
        self.engine = None
        self.srv = None
        self.url = None
        self.fetches = JitFetches()

    def open(self, p_dir: str):
        from dgraph_tpu import cli

        self.engine = cli._server(argparse.Namespace(
            p=p_dir, storage="backend=lsm", encryption_key_file=None))
        return self.engine

    def serve(self) -> str:
        from dgraph_tpu.api.http_server import HTTPServer

        self.srv = HTTPServer(self.engine, host="127.0.0.1", port=0).start()
        self.url = f"http://127.0.0.1:{self.srv.port}"
        return self.url

    def close(self) -> None:
        from dgraph_tpu.worker import applyshard

        if self.srv is not None:
            self.srv.stop()
            self.srv = None
        if self.engine is not None:
            self.engine.kv.close()
            self.engine = None
        applyshard.shutdown()
