"""Seconds of set-up inside `valcol.build` spans: one scan of a
predicate's data keys, the ranks of its values, the upload (query/
valcol.py). The sum of the alpha's `span_valcol.build_seconds`
histogram, read after the window, so a build inside the window is in it
too (`value_column_builds_total` says whether one was). None where no
such span finished. Layer: level reads. Moves: setup_s."""

from chipbench import spans


def read(ctx):
    return spans.phase_seconds("valcol.build")
