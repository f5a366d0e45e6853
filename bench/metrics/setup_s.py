"""Process start to the window's start: loading, weights, compiling (or
reading the compile cache) and warming up, in seconds."""


def read(run):
    return run.setup_s
