"""Milliseconds each search spent in the quantized rerank outside its
reads: bounds, watermark, survivor selection, exact scoring and staging
(``SearchStats.rerank_s``, the ``ecp.rerank`` spans), mean over the
window's answered searches."""
from bench.lib.searches import mean


def read(run):
    return mean(run, "rerank_s", 1e3)
