"""Median of each search's wait in the server's admission queue, from
submit to a worker taking it (``SearchStats.queue_wait_s``), in
milliseconds."""
from bench.lib.searches import median


def read(run):
    return median(run, "queue_wait_s", 1e3)
