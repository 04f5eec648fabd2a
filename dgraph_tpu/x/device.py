"""The device this process serves from, as jax reports it.

CPU only when asked for (`JAX_PLATFORMS=cpu`, as the tests run).
Otherwise an accelerator is required: a backend that fails to
initialize raises to the caller, and jax's own no-accelerator fallback
to CPU is an error here — at alpha start-up and on the request — never
a switch to the host kernels. Every device/host choice in the engine
(query/dispatch.py thresholds, models/vector.py engine pick) reads the
platform from here, so none of them can hide a missing chip.
"""

from __future__ import annotations

import functools
import types
from typing import Mapping, Union


class NoAcceleratorError(RuntimeError):
    """jax initialized, found no accelerator and fell back to CPU
    without being asked to."""


def _asked_for() -> str:
    """First entry of jax's platform list (JAX_PLATFORMS), "" if unset."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0].strip()


@functools.lru_cache(maxsize=None)
def info() -> Mapping[str, Union[str, int]]:
    """{"platform", "device_kind", "device_count"} of the default
    backend (read-only: every caller gets the one cached mapping). The
    first call initializes the backend; a failure propagates and is not
    cached (lru_cache keeps results, not exceptions), so every request
    keeps failing until the backend comes up."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform == "cpu" and _asked_for() != "cpu":
        raise NoAcceleratorError(
            "jax found no accelerator and fell back to cpu; set "
            "JAX_PLATFORMS=cpu to serve from the host kernels on purpose"
        )
    return types.MappingProxyType({
        "platform": platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    })


def platform() -> str:
    return str(info()["platform"])


def describe() -> str:
    """One line naming the device, for the alpha's start-up output."""
    d = info()
    return (
        f"serving from {d['platform']} ({d['device_kind']} x "
        f"{d['device_count']})"
    )
