"""Bytes each search's grouped-kernel launches hand the device after
the kernel's padding (``SearchStats.h2d_bytes``), mean over the window's
answered searches."""
from bench.lib.searches import mean


def read(run):
    return mean(run, "h2d_bytes")
