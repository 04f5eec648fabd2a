"""Seconds of set-up inside `ivf.assign` spans: every row to its two
nearest cells (`models/vector._assign_top2`). The sum of the alpha's
`span_ivf.assign_seconds` histogram, read after the window (see
`ivf_kmeans_s`). None where no such span finished. Layer: vector index.
Moves: setup_s."""

from chipbench import spans


def read(ctx):
    return spans.phase_seconds("ivf.assign")
