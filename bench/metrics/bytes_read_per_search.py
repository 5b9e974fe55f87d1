"""Bytes the blob store read in the window (``IOStats.bytes_read`` of the
views the server read through), over the searches answered."""


def read(run):
    n = len(run.answered("search"))
    return run.bytes_read / n if n else None
