"""Seconds of set-up inside `ivf.kmeans` spans: the sampled mini-batch
k-means that places the IVF cells' centroids
(`models/vector._train_centroids`). The sum of the alpha's
`span_ivf.kmeans_seconds` histogram, read after the window: the build
runs once, inside the first `similar_to`, which `index_build_s` times
from outside. None where no such span finished. Layer: vector index.
Moves: setup_s."""

from chipbench import spans


def read(ctx):
    return spans.phase_seconds("ivf.kmeans")
