"""Test configuration: the CPU platform with a virtual 8-device mesh.

Tests exercise the same code paths on a CPU backend with 8 virtual
devices so multi-chip sharding is validated without TPU hardware
(mirrors the reference's docker-on-one-host integration strategy,
/root/reference TESTING.md). The chip itself is reached only through
`python chip_smoke.py` on a machine that has one.
"""

import os

# CPU by request: without it the engine refuses to serve when jax finds
# no accelerator (dgraph_tpu/x/device.py)
os.environ["JAX_PLATFORMS"] = "cpu"

# XLA_FLAGS is read at CPU client creation (first backend init), which
# happens after conftest — still in time to set it here.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# XLA/TSL C++ logging writes to RAW stderr (bypassing pytest capture):
# a cold compile-cache INFO mid-run splices into the progress-dot lines
# and corrupts dot-counting harnesses. Level 2 keeps ERROR visible.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

# importing the package places the persistent compile cache
# (dgraph_tpu/__init__.py); it must happen before jax is imported
import dgraph_tpu  # noqa: E402,F401


# GIL-fuzz race harness (DGRAPH_TPU_RACE_FUZZ / check.sh --race-sanity):
# a ~1µs switch interval forces a thread switch roughly every bytecode,
# so a read-modify-write race that needs an unlucky preemption between
# LOAD and STORE hits on nearly every iteration instead of once a month
# under full-suite load. Env read is raw on purpose — conftest runs
# before dgraph_tpu imports are safe, and tests/ is outside the
# config-registry analyzer's scan root.
if os.environ.get("DGRAPH_TPU_RACE_FUZZ", "").strip().lower() in (
    "1", "true", "yes", "on"
):
    import sys as _sys

    _sys.setswitchinterval(1e-6)


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: the parallel-executor smoke subset
    # (test_parallel_exec.py, DGRAPH_TPU_EXEC_WORKERS=4 over sampled DQL
    # goldens) stays in tier-1 to keep thread-safety regressions out of
    # main; the full 535-case corpus sweep and other large passes carry
    # this marker so the 1-core box stays fast.
    config.addinivalue_line(
        "markers",
        "slow: full-corpus / large-scale passes excluded from tier-1",
    )
    # chaos: fault-injection suites (tests/test_chaos.py). The fixed-seed
    # smoke schedules stay in tier-1 (<30s); long randomized schedules
    # carry `slow` as well and run out-of-band.
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection schedules against the cluster stack",
    )
