"""Mean host CPU time the vector index spends applying one committed
write to its device snapshot: the self CPU of the `ivf.apply` spans
(the host side: draining the pending rows, placing them in slabs,
padding) and of their `ivf.apply.launch` children, summed over the
traced `/query` records (a search applies the writes pending when it
comes), over the writes those spans applied (their `writes` attr). None
where no traced record applied a write (a program without these
spans). Layer: vector index. Moves: qps."""

from chipbench import spans

NAMES = ("ivf.apply", "ivf.apply.launch")


def read(ctx):
    recs = spans.records(ctx)
    if not recs:
        return None
    writes = sum(r["attrs"].get("ivf.apply.writes", 0) for r in recs)
    if not writes:
        return None
    return sum(spans.self_cpu(r, NAMES) for r in recs) / writes
