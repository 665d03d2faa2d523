"""Process start to the window's first due request: imports, weights from
the seed, engine construction and the warm-up of every shape the mix can
reach (compilation, or loading from the persistent cache)."""


def read(run):
    return run.setup_s
