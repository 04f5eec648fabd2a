"""The value column programs' share of their memory roofline. Least
time = the bytes the column programs of one request must move once
(`column_bytes`: each padded candidate id in, its gathered key, its mask
or kept flag out; NOT the whole column, which a binary search does not
read) over the chip's HBM bandwidth; divided by the device's busy time
per request from the trace. The programs are bound by latency and by
the gather, so the share is small; it is here so that a later change
can move it. None where a traced request launched any other family, or
where the program opens no `valcol.pad` span. Layer: kernels. Moves:
qps."""

from chipbench import spans


def column_bytes(padded_ids: float, uses: float = 1.0) -> float:
    """Bytes `uses` column programs over `padded_ids` candidates each
    have to move once: a uint32 id in, its int32 key gathered, one byte
    of mask out."""
    return uses * padded_ids * (4 + 4 + 1)


def read(ctx):
    t = ctx["trace"]
    recs = spans.records(ctx)
    if not recs or not t or t["busy_s"] <= 0 or not ctx["traced_requests"]:
        return None
    if not ctx["fetches"] or any(
            not k.startswith("setop:column_") for k in ctx["fetches"]):
        return None  # another family shares the busy time
    padded = spans.mean(
        ctx, lambda r: float(r["attrs"].get("valcol.pad.padded", 0)))
    if not padded:
        return None
    kind = ctx["device_kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"no peaks for device {kind!r} in peaks.json")
    least_s = column_bytes(padded) / ctx["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["busy_s"] / ctx["traced_requests"])
