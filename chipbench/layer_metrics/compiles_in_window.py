"""Programs compiled inside the measured window (``jax.monitoring`` compile
events, persistent-cache hits included): warm-up's claim is 0."""


def read(view):
    return float(view.record["compiles_in_window"])
