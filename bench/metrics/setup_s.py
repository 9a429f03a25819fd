"""setup_s: process start to the window's opening: data, index load (or
build), the serving stack and the warm-up compiles."""


def read(run):
    return run.setup_s
