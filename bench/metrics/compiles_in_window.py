"""Backend compiles (``jax.monitoring`` events) inside the window. Warm-up
runs the cell's own traffic, so this reads 0 unless a shape was missed."""


def read(run):
    return float(run.compiles)
