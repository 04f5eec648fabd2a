"""From a jax.profiler trace (.xplane.pb) to the device's busy time.

Busy is the union of the intervals in which an operation ran on a device
plane (`/device:TPU:<n>`), taken from the plane's "XLA Ops" line — the
lines "Steps", "XLA Modules" and the name-scope lines repeat the same
time at other grains and are not added. It needs no kernel names. Busy
seconds are averaged over the device planes found. No device plane, or
no operation on one, gives busy 0.0 and the harness's result says so;
it is never a default.

`python3 -m chipbench.trace_reduce <dir or .xplane.pb>` prints the
reduction and the planes and lines it saw.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_seconds(intervals) -> tuple:
    """(seconds covered, merged [(start, end)]) of [(start_ns, end_ns)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") or name.startswith("/device:GPU:")


def reduce_planes(planes) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])] -> busy seconds and the breakdown."""
    busy, ops, gaps = [], collections.Counter(), collections.Counter()
    seen = []
    for pname, lines in planes:
        seen.append([pname, [ln for ln, _ in lines]])
        if not is_device_plane(pname):
            continue
        by_name = dict(lines)
        op_events = by_name.get(OPS_LINE)
        if op_events is None:  # no ops line: every line of the plane
            op_events = [ev for _, evs in lines for ev in evs]
        secs, merged = union_seconds(
            (s, s + d) for _, s, d in op_events if d > 0)
        busy.append(secs)
        for name, _, d in op_events:
            ops[name] += d / 1e9
        # an idle gap is named for the program whose op ended it:
        # "before:" where that program started after the gap began (the
        # device waited for the host to launch it), "within:" otherwise
        modules = sorted((s, s + d, name) for name, s, d in
                         by_name.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            i = bisect.bisect_right(starts, s1) - 1
            if i < 0 or s1 >= modules[i][1]:
                label = "before:?"
            else:
                label = (("before:" if modules[i][0] >= e0 else "within:")
                         + modules[i][2].split("(")[0])
            gaps[label] += (s1 - e0) / 1e9
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "device_planes": len(busy),
        "top_ops": [[n[:120], s] for n, s in ops.most_common(10)],
        "top_gaps": [[n, s] for n, s in gaps.most_common(10)],
        "planes": seen,
    }


def read_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                 for ev in ln.events])
                      for ln in p.lines])
            for p in data.planes]


def reduce_dir(path: str) -> dict:
    files = ([path] if os.path.isfile(path) else
             glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                       recursive=True))
    if not files:
        return reduce_planes([])
    if files[0].endswith(".json"):  # a recorded trace, already as planes
        with open(files[0]) as f:
            return reduce_planes(json.load(f))
    return reduce_planes(read_xplane(sorted(files)[-1]))


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1]), indent=1))
