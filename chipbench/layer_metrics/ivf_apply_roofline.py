"""The vector index's update programs' share of their memory roofline.
Least time = the bytes those programs must move once (`apply_bytes`:
each appended slab row's vector, norm and id written, each appended
corpus row's vector, norm and valid flag written, each tombstone's id
and valid flag written, and the centroid table read once by each cell
assignment), over the window's update programs (the maker's counters,
`chipbench/data/mog_live.py` `describe`), over the chip's HBM bandwidth;
divided by the device time of an update program in the traced stretch:
the programs the `ivf.apply.launch` spans enqueued, matched to the
device's programs by order as `span_reduce.order_offset` matches them
(`mog_live.apply_device`). Memory-bound: a scatter of a few rows and a
matmul of a few rows against the centroids. None where the trace has no
device plane, or the match cannot separate them, or no update program
fell in the traced stretch. Layer: kernels. Moves: qps."""

from chipbench import write_spans


def apply_bytes(appended: float, tombstoned: float, assigns: float,
                dim: int, nlist: int) -> float:
    """Bytes the update programs have to move once: `appended` and
    `tombstoned` slab rows (two a row), `assigns` cell assignments over
    `nlist` centroids of `dim` float32."""
    return (appended * (dim * 4 + 4 + 4)
            + appended / 2 * (dim * 4 + 4 + 1)
            + tombstoned * 4 + tombstoned / 2
            + assigns * nlist * (dim * 4 + 4))


def read(ctx):
    d = ctx.get("describe") or {}
    seconds, traced = d.get("apply_device_s"), d.get("apply_programs_traced")
    programs = write_spans.in_window(ctx, "vector_ivf_apply_programs_total")
    if not seconds or not traced or not programs or "nlist" not in d:
        return None
    kind = ctx["device_kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"no peaks for device {kind!r} in peaks.json")
    moved = apply_bytes(
        write_spans.in_window(ctx, "vector_ivf_appended_rows_total"),
        write_spans.in_window(ctx, "vector_ivf_tombstoned_rows_total"),
        write_spans.in_window(
            ctx, 'device_dispatch_total{family="vec.ivf_assign"}'),
        d["dim"], d["nlist"])
    least_s = moved / programs / ctx["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / traced)
