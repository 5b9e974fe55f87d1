"""Milliseconds each search waited on its grouped-kernel launches, from
the call to its numpy results: padding, transfer, launch and device
time (``SearchStats.kernel_s``, the ``ecp.kernel`` spans), mean over the
window's answered searches."""
from bench.lib.searches import mean


def read(run):
    return mean(run, "kernel_s", 1e3)
