"""Backend compiles inside the measured window (``jax.monitoring``); the
warm-up should leave none."""


def read(w):
    return w.compiles
