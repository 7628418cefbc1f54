"""Seconds from process start to the start of the window: starting JAX,
building the tables, and the warm-up pass that compiles or loads every
program from the cache."""


def read(run):
    return run.setup_s
