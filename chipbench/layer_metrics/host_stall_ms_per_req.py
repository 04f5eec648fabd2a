"""Mean wall time of a request in which its thread neither ran nor
waited for the device: the root span's wall time less all self CPU time
and less the wall time of the `*.wait` spans. Runnable and not running:
the GIL, the scheduler, a blocking socket. A mean, because a CPU time is
in it (`chipbench/spans.py`). Layer: host threads. Moves:
latency_p50_ms."""

from chipbench import spans


def read(ctx):
    return spans.mean(
        ctx, lambda r: r["wall_ms"] - spans.host_cpu(r) - spans.wait_wall(r))
