"""setup_s: seconds from the start of the run's process to the end of one
warm-up call on the window's own path (imports, data and weights from the
seed, the program's build and its compile or compile-cache load)."""


def read(ctx):
    return ctx["setup_s"]
