"""Process start to window start: weights, runtime, compiling or loading
every step program, and the ramp."""


def read(run):
    return run.setup_s
