"""XLA backend compilations (jax.monitoring) between the window's first
request and its last answer; expected 0. Layer: device boundary. Moves:
qps."""


def read(ctx):
    return float(ctx["compiles_in_window"])
