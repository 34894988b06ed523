"""Seconds from the process's start to the window's: imports, JAX's
start-up, making the data, committing the history, compiles or loads from
the persistent cache, and the warm-up."""


def read(w):
    return w.setup_s
