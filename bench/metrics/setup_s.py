"""Seconds from process start to the first timed request: JAX start-up,
data made on the device, the request pool, warm-up from the compile
cache."""


def read(run):
    return run.setup_s
