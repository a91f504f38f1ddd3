"""Programs compiled (or read back from the persistent cache) inside the
measured window, from ``jax.monitoring``: a step signature that set-up
did not warm."""


def read(run):
    return float(run["compiles_in_window"])
