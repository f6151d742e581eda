"""Process start to the first request due: imports, JAX start-up, data,
fitted and compiled model (from the cache after a checkout's first run),
placement on the device and the warm-up of every bucket."""


def read(run):
    return run.setup_s
