"""Share of the node cache's lookups in the window that hit
(``NodeCache.hits / (hits + misses)``), in percent."""


def read(run):
    total = run.cache_hits + run.cache_misses
    return 100.0 * run.cache_hits / total if total else None
