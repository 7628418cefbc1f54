"""Rows of every table each program of the window read, over the seconds
from the window's start to its last completion."""


def read(run):
    return sum(c.rows for c in run.calls) / (run.window_end - run.window_start)
