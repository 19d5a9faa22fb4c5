"""Backend compiles inside the window (jax.monitoring's compile events):
the warm-up should have left none."""


def compute(run):
    return float(run.window_compiles)
