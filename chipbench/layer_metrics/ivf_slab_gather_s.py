"""Seconds of set-up inside `ivf.slab_gather` spans: the rows sorted by
cell and copied into the padded slabs the probe reads. The sum of the
alpha's `span_ivf.slab_gather_seconds` histogram, read after the window
(see `ivf_kmeans_s`). None where no such span finished. Layer: vector
index. Moves: setup_s."""

from chipbench import spans


def read(ctx):
    return spans.phase_seconds("ivf.slab_gather")
