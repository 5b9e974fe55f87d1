"""Seconds from the process's start to the window's opening: generate,
build, convert, open, compile or load every kernel shape, warm up."""


def read(run):
    return run.setup_s
