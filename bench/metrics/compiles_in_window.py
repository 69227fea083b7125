"""Programs compiled inside the measured window (``jax.monitoring``'s
backend-compile events); 0 when the warm-up covered every shape."""


def read(record):
    return record["compiles_in_window"]
