"""1 minus the union of busy intervals on the device plane over the
traced window. Layer: device. Moves: qps."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["device_planes"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
