"""Backend compiles reported by JAX inside the window: there should be
none, since set-up warms every program."""


def read(run):
    return len(run.clock.backend_compiles(run.window_start, run.window_end))
