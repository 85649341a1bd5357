"""From process start to the window's start: imports, the chip, the store,
compiling or loading from the cache, and one warm query."""


def read(run):
    return run.setup_s
