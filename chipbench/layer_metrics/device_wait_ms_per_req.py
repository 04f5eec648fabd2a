"""Median wall time a request spends blocked on the device: the spans
`setop.wait` and `vec.wait` (`np.asarray` of the outputs), which hold
the queue behind other requests' programs, the execution and the
read-back. It is no less than `device_busy_ms_per_req`. Layer: device
boundary. Moves: latency_p50_ms."""

from chipbench import spans


def read(ctx):
    return spans.median(ctx, spans.wait_wall)
