"""Process start to the first due request: imports, weights, gateway,
warm-up of every program shape (compiling or loading each)."""


def read(run):
    return run.setup_s
