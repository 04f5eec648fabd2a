"""The IVF probe's share of its memory roofline. Least time = the
float32 bytes one query's probe must read (the rows of the slabs it
gathers, their norms and row ids, and the centroid table it scores;
`probe_bytes` below, from the index's static slab shape) over the
chip's HBM bandwidth; divided by the device's busy time per request
from the trace. Memory-bound: 2 flops a byte. Layer: kernels. Moves:
qps."""


def probe_bytes(probe_rows: int, dim: int, nlist: int) -> int:
    """Bytes the probe of ONE query has to read once: each gathered row
    (dim float32), its squared norm and row id; each centroid and its
    squared norm."""
    return probe_rows * (dim * 4 + 8) + nlist * (dim * 4 + 4)


def read(ctx):
    d, t = ctx["describe"], ctx["trace"]
    if not d.get("probe_rows") or not t or t["busy_s"] <= 0:
        return None
    tiers = {k for k in ctx["fetches"] if k.startswith("vector:")}
    if not ctx["traced_requests"] or not tiers or any(
            not k.startswith("vector:ivf") for k in tiers):
        return None  # some request took another tier: nothing sound to read
    kind = ctx["device_kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"no peaks for device {kind!r} in peaks.json")
    least_s = (probe_bytes(d["probe_rows"], d["dim"], d["nlist"])
               / ctx["peaks"][kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["busy_s"] / ctx["traced_requests"])
