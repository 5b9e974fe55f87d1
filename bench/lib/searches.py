"""Readings of the program's own per-search ``SearchStats`` fields over
the window's answered searches (each page's stats as they stood when it
came back).  A program whose stats lack the field gives nothing."""
import numpy as np


def values(run, field: str) -> list | None:
    """``stats.<field>`` of every answered search, or None where the
    program does not keep that field."""
    vals = [getattr(r.stats, field, None) for r in run.answered("search") if r.stats is not None]
    vals = [float(v) for v in vals if v is not None]
    return vals or None


def median(run, field: str, scale: float = 1.0) -> float | None:
    vals = values(run, field)
    return float(np.percentile(vals, 50)) * scale if vals else None


def mean(run, field: str, scale: float = 1.0) -> float | None:
    vals = values(run, field)
    return float(np.mean(vals)) * scale if vals else None
