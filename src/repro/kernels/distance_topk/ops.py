"""Public op: distance_topk — jit'd wrapper choosing kernel vs reference.

On TPU the Pallas kernel runs compiled; on CPU it is validated with
``interpret=True``. ``impl="auto"`` uses the reference path on CPU (fast)
and the compiled kernel on TPU, so callers never branch themselves;
``resolve_impl`` says which one a call will take.
"""
from __future__ import annotations

import jax

from .distance_topk import distance_topk_pallas
from .grouped import grouped_distance_topk_pallas
from .ref import distance_topk_ref, grouped_distance_topk_ref


def resolve_impl(impl: str = "auto") -> str:
    """The implementation ``impl`` runs as: "auto" is the compiled Pallas
    kernel ("pallas") on a TPU and the numpy/jnp reference ("ref")
    elsewhere; any other value is taken as given."""
    if impl == "auto":
        return "pallas" if jax.devices()[0].platform == "tpu" else "ref"
    return impl


def distance_topk(q, c, k: int, metric: str = "l2", *, impl: str = "auto", **kw):
    """q [B, D], c [N, D] -> (dists [B, k], idx [B, k]), ascending distance.

    impl: "auto" | "ref" | "pallas" | "pallas_interpret"
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return distance_topk_ref(q, c, k, metric)
    if impl == "pallas":
        return distance_topk_pallas(q, c, k, metric, **kw)
    if impl == "pallas_interpret":
        return distance_topk_pallas(q, c, k, metric, interpret=True, **kw)
    raise ValueError(f"unknown impl {impl!r}")


def grouped_distance_topk(
    q,
    codes,
    scales,
    offsets,
    n_rows,
    k: int,
    metric: str = "l2",
    qformat: str = "int8",
    *,
    impl: str = "auto",
    **kw,
):
    """One device launch for a whole traversal round: group g scores
    q[g] against its leaf's quantized codes[g].  Returns numpy
    (dists [G, k], idx [G, k]); invalid tail entries are (inf, -1).

    impl: "auto" | "ref" | "pallas" | "pallas_interpret"
    """
    import numpy as np

    impl = resolve_impl(impl)
    if impl == "ref":
        d, i = grouped_distance_topk_ref(
            q, codes, scales, offsets, n_rows, k, metric, qformat
        )
    elif impl == "pallas":
        d, i = grouped_distance_topk_pallas(
            q, codes, scales, offsets, n_rows, k, metric, qformat, **kw
        )
    elif impl == "pallas_interpret":
        d, i = grouped_distance_topk_pallas(
            q, codes, scales, offsets, n_rows, k, metric, qformat, interpret=True, **kw
        )
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return np.asarray(d), np.asarray(i)
