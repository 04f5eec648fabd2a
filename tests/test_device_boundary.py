"""The device boundary never hides a missing chip (x/device.py), the
compile cache is placed from outside, and chip_smoke.py keeps its
contract off the chip: it refuses to run, fast and silently.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, env_extra=None, env_drop=(), cwd=REPO, timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_extra or {})
    return subprocess.run(
        argv, cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


# -- x/device.py --------------------------------------------------------------


@pytest.fixture
def fresh_device():
    """device.info() caches its answer; tests that fake the backend
    start and end with an empty cache."""
    from dgraph_tpu.x import device

    device.info.cache_clear()
    yield device
    device.info.cache_clear()


def _boom():
    raise RuntimeError("backend init failed: no chip answered")


def test_dispatcher_raises_when_backend_init_raises(monkeypatch, fresh_device):
    import jax

    from dgraph_tpu.query.dispatch import SetOpDispatcher

    monkeypatch.setattr(jax, "devices", _boom)
    d = SetOpDispatcher()
    a = np.arange(10, dtype=np.uint64)
    with pytest.raises(RuntimeError, match="no chip answered"):
        d.run_pairs("intersect", [(a, a)])
    with pytest.raises(RuntimeError, match="no chip answered"):
        d.run_rows_vs_one("intersect", [a], a)
    with pytest.raises(RuntimeError, match="no chip answered"):
        d.run_rows_vs_one_ragged(
            "intersect", a, np.asarray([0, 10], np.int64), a)
    with pytest.raises(RuntimeError, match="no chip answered"):
        d.run_chain("union", [a, a])
    # the failure is not remembered as "use the host": it keeps failing
    with pytest.raises(RuntimeError, match="no chip answered"):
        d.run_pairs("intersect", [(a, a)])


def test_vector_index_raises_when_backend_init_raises(
    monkeypatch, fresh_device
):
    import jax

    from dgraph_tpu.models.vector import VectorIndex

    ix = VectorIndex("emb")
    rng = np.random.default_rng(0)
    V = rng.standard_normal((5000, 8)).astype(np.float32)  # quant-engine size
    ix.bulk_load(np.arange(1, 5001, dtype=np.uint64), V)
    monkeypatch.setattr(jax, "devices", _boom)
    with pytest.raises(RuntimeError, match="no chip answered"):
        ix.search(V[0], 3)
    with pytest.raises(RuntimeError, match="no chip answered"):
        ix.search_batch(V[:2], 3)


def test_unrequested_cpu_is_an_error(monkeypatch, fresh_device):
    """jax's own no-accelerator fallback: the backend comes up as cpu
    although nobody asked for the cpu."""
    from dgraph_tpu.query.dispatch import SetOpDispatcher

    monkeypatch.setattr(fresh_device, "_asked_for", lambda: "tpu")
    with pytest.raises(fresh_device.NoAcceleratorError):
        fresh_device.info()
    a = np.arange(10, dtype=np.uint64)
    with pytest.raises(fresh_device.NoAcceleratorError):
        SetOpDispatcher().run_pairs("intersect", [(a, a)])


def test_requested_cpu_serves_from_host_kernels(fresh_device):
    from dgraph_tpu.query import dispatch

    info = fresh_device.info()
    assert info["platform"] == "cpu" and info["device_count"] >= 1
    d = dispatch.SetOpDispatcher()
    assert d._min_total() == dispatch._HOST_ONLY
    a = np.arange(0, 100, dtype=np.uint64)
    b = np.arange(0, 100, 2, dtype=np.uint64)
    assert np.array_equal(d.run_pairs("intersect", [(a, b)])[0], b)
    assert not d._jit_cache  # nothing was compiled for it


def test_health_names_the_device():
    from dgraph_tpu.api.http_server import HTTPServer
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.client import DgraphClient
    from dgraph_tpu.x import device

    srv = HTTPServer(Server(), port=0).start()
    try:
        h = DgraphClient(f"http://127.0.0.1:{srv.port}").health()[0]
    finally:
        srv.stop()
    assert {k: h[k] for k in ("platform", "device_kind", "device_count")} \
        == device.info()
    assert device.describe().startswith("serving from cpu")


def test_native_build_failure_is_an_error_where_required():
    got = _run(
        [sys.executable, "-c",
         "from dgraph_tpu import native\n"
         "assert not native.NATIVE_AVAILABLE and native.BUILD_ERROR\n"
         "native.require()"],
        env_extra={"DGRAPH_TPU_NATIVE_SAN": "no-such-sanitizer"},
    )
    assert got.returncode != 0
    assert "native host kernels failed to build" in got.stderr


# -- compile cache placement ---------------------------------------------------

_PRINT_CACHE = (
    "import dgraph_tpu, jax; print(jax.config.jax_compilation_cache_dir)"
)


def test_compile_cache_env_is_used_untouched(tmp_path):
    want = str(tmp_path / "elsewhere")
    got = _run([sys.executable, "-c", _PRINT_CACHE],
               env_extra={"JAX_COMPILATION_CACHE_DIR": want})
    assert got.returncode == 0, got.stderr
    assert got.stdout.strip() == want
    assert not os.path.exists(want)  # nothing created behind jax's back


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    outs = [
        _run([sys.executable, "-c", _PRINT_CACHE],
             env_extra={"PYTHONPATH": REPO},
             env_drop=("JAX_COMPILATION_CACHE_DIR",), cwd=cwd)
        for cwd in (REPO, str(tmp_path))  # two processes, two cwds
    ]
    for got in outs:
        assert got.returncode == 0, got.stderr
    paths = {got.stdout.strip() for got in outs}
    assert paths == {os.path.join(REPO, ".jax_cache")}
    ignored = _run(["git", "check-ignore", "-q", ".jax_cache/x"])
    if ignored.returncode != 128:  # 128: not a git checkout (the chip copy)
        assert ignored.returncode == 0, ".jax_cache/ must be git-ignored"


# -- chip_smoke.py off the chip -------------------------------------------------


def test_chip_smoke_refuses_without_a_chip_before_building_data():
    t0 = time.monotonic()
    got = _run([sys.executable, SMOKE], env_extra={"JAX_PLATFORMS": "cpu"},
               timeout=60)
    assert got.returncode != 0
    assert got.stdout == ""  # no result of any kind
    assert "no TPU" in got.stderr
    assert time.monotonic() - t0 < 30


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    got = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
               env_drop=("PYTHONPATH",), timeout=60)
    assert got.returncode != 0
    assert got.stdout == ""


def test_chip_smoke_rehearsal_passes_on_cpu_and_says_cpu():
    """The same code path at a tiny size; the TPU threshold is imposed
    from outside so the jitted set-op families really run."""
    got = _run(
        [sys.executable, SMOKE, "--rehearsal", "--persons", "3000",
         "--vectors", "2000", "--dim", "16"],
        env_extra={"JAX_PLATFORMS": "cpu",
                   "DGRAPH_TPU_DEVICE_MIN_TOTAL": "32768"},
        timeout=600,
    )
    assert got.returncode == 0, got.stdout[-4000:] + got.stderr[-4000:]
    last = json.loads(got.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert "FAIL" not in got.stdout
    for family in ("intersect", "difference", "union", "intersect#shared",
                   "difference#shared", "intersect#chain", "union#chain"):
        assert f'PASS kernel:{family} ' in got.stdout
