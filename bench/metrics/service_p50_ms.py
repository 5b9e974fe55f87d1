"""Median of each search's time on a server worker, snapshot lease
included (``SearchStats.service_s``, the ``serve.search`` span), in
milliseconds."""
from bench.lib.searches import median


def read(run):
    return median(run, "service_s", 1e3)
