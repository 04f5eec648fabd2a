"""Benchmark artifact provenance + overwrite protection.

Every benchmark JSON this repo writes carries a `provenance` block (git
SHA, UTC timestamp, platform) so a number on disk can always be traced
to the commit and backend that produced it — and a TPU-captured
artifact can never be silently clobbered by a CPU rerun.
"""

import json
import os
import subprocess
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def provenance(platform: str) -> dict:
    try:
        sha = (
            subprocess.run(
                ["git", "rev-parse", "HEAD"],
                cwd=REPO, capture_output=True, text=True, timeout=10,
            ).stdout.strip()
            or "unknown"
        )
        dirty = bool(
            subprocess.run(
                ["git", "status", "--porcelain"],
                cwd=REPO, capture_output=True, text=True, timeout=10,
            ).stdout.strip()
        )
    except Exception:
        sha, dirty = "unknown", False
    return {
        "git_sha": sha,
        "git_dirty": dirty,
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": platform,
        "jax_platforms_env": os.environ.get("JAX_PLATFORMS", ""),
    }


def is_tpu(platform: str) -> bool:
    return "tpu" in (platform or "").lower()


def guarded_write(path: str, obj: dict, platform: str) -> str:
    """Write obj+provenance to path — unless path already holds a
    TPU-platform artifact and this run is on the CPU, in which case
    the new data lands at `<path>.cpu.json` and the TPU capture stays.
    Returns the path actually written."""
    obj = dict(obj)
    obj["provenance"] = provenance(platform)
    if os.path.exists(path) and not is_tpu(platform):
        try:
            old = json.load(open(path))
            if is_tpu(
                (old.get("provenance") or {}).get("platform", "")
            ):
                alt = path + ".cpu.json"
                with open(alt, "w") as f:
                    json.dump(obj, f, indent=1)
                print(
                    f"[stamp] {path} holds a TPU capture; cpu run "
                    f"written to {alt}"
                )
                return alt
        except Exception:
            pass
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path
