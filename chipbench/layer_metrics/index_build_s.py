"""Wall seconds of the first `similar_to`: host IVF build, upload,
compile or cache load. Layer: vector index. Moves: setup_s."""


def read(ctx):
    return ctx["install"].get("index_build_s")
