"""Programs the backend had to build inside the window
(``jit_compilations``); set-up warms every shape, so this reads 0."""
from bench.window import delta


def read(rec):
    return delta(rec, "jit_compilations")
