"""XLA programs compiled, or loaded from the persistent cache, inside the
measured window (jax's monitoring events); 0 when set-up warmed every
shape the window used."""


def read(run):
    return float(run.window_compiles)
