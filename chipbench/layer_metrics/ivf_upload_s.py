"""Seconds of set-up inside `ivf.upload` spans: the corpus matrix
(`_sync_device`) and the IVF's slabs, copied to the device. The sum of
the alpha's `span_ivf.upload_seconds` histogram, read after the window
(see `ivf_kmeans_s`). None where no such span finished. Layer: vector
index. Moves: setup_s."""

from chipbench import spans


def read(ctx):
    return spans.phase_seconds("ivf.upload")
