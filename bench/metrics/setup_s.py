"""Set-up time: from the start of the process to the first timed request
(JAX start, data generation, indexes, engine, warm-up of every shape)."""


def read(run):
    return run.setup_s
