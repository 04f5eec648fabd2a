"""Median count of `setop.launch` and `vec.launch` spans in a request:
the programs it enqueued on the device, counted by the program where it
enqueues them (`device_ops_per_req` counts the same from outside). 0.0
is a reading. Layer: device boundary. Moves: qps."""

from chipbench import spans


def read(ctx):
    return spans.median(ctx, spans.launches)
