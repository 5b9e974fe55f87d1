"""Milliseconds each search spent packing its rounds' codes for the
grouped kernel (``SearchStats.pack_s``, the ``ecp.pack`` spans), mean
over the window's answered searches."""
from bench.lib.searches import mean


def read(run):
    return mean(run, "pack_s", 1e3)
