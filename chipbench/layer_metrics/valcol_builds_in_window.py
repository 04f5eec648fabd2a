"""Value columns built inside the window: the rise of the program's
`value_column_builds_total` between the window's first request and its
last answer, as the maker reports it (`chipbench/data/snb_mixed.py`
`describe`). A column that follows the commits builds none; one that a
commit drops is scanned again (3.6 s of one predicate at SF1's message
tenth). Layer: device boundary. Moves: qps."""

from chipbench import write_spans


def read(ctx):
    return write_spans.in_window(ctx, "value_column_builds_total")
