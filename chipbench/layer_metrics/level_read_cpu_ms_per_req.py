"""Mean CPU time a request spends reading posting lists, one
`level_task` span per predicate and level (MemoryLayer, LSM, decode):
their self CPU, on whichever thread ran them. Layer: level reads. Moves:
qps."""

from chipbench import spans


def read(ctx):
    return spans.mean_self_cpu(ctx, ("level_task",))
