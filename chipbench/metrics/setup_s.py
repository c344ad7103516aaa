"""Seconds from the start of the process to the first timed step: build,
compile or cache load, warm-up and the stationary start."""


def read(run):
    return run.setup_s
