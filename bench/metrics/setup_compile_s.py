"""Seconds JAX spent tracing, lowering and compiling (or loading from the
cache) during set-up."""


def read(run):
    return run.clock.seconds(run.process_start, run.window_start)
