"""Wall seconds from `cli._server` to the engine answering its first
query: bulk load and sync where the run had to load, the reopening of
the kept store otherwise. Layer: level reads. Moves: setup_s."""


def read(ctx):
    open_s = ctx["install"].get("open_s")
    if open_s is None:
        return None
    return open_s + ctx["warm"]["first_round_s"]
