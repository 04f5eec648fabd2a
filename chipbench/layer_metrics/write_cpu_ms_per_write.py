"""Mean CPU time of a write's own work over the traced `/mutate`
requests: self CPU of the `mutate` subtree (`mutate`, `mutate.parse`,
`mutate.apply`, and the commit's work on the request's thread, which
`mutate`'s self CPU holds: `commit.wait` and `commit` read no CPU clock
of their own). The wire's spans are not in it. None where no traced
write has a `mutate` span (a program from before it). Layer: commit.
Moves: qps."""

from chipbench import spans, write_spans


def read(ctx):
    return write_spans.mean(
        ctx, lambda r: spans.self_cpu(r, write_spans.MUTATE))
