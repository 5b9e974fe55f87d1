"""Pallas TPU kernel: grouped quantized distance + running top-k.

The batched traversal's per-round launch (core/search.py): each group g
is one (query, leaf) scan unit — query row ``q[g]`` against that leaf's
quantized codes — and ALL units in a round go up in a single
``pallas_call`` instead of one kernel launch per leaf.  Groups are
independent (grid axis 0 is parallel); the candidate axis reuses the
running-top-k scratch pattern of ``distance_topk``.

Inputs are padded to a common leaf size: codes [G, N_pad, D] in the
quantized dtype (int8 | float16), per-group dequant params (scale,
offset — f32, exactly as the blob companion stores them) and per-group
valid row counts (int32).  int8 dequantization happens in-kernel right
before the MXU, so HBM only holds the compressed codes.  float16 codes
are widened to f32 before the ``pallas_call`` (the cast is exact; TPU
v5e's Mosaic cannot load f16 vectors), so they cross PCIe as f16 but
sit in HBM as f32.

Every per-group array carries a leading group axis that the BlockSpecs
squeeze (``None``), so each block's last two dims equal the array's —
what the TPU's (8, 128) tiling rule requires of a one-row block.

The host wrapper pads N to a multiple of ``bn`` and G to a power of two
before the jitted call: a traversal round's leaf sizes and unit counts
vary, and each distinct shape would otherwise be a fresh compile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .distance_topk import _merge_topk

NEG_ONE = -1


def _gkernel(
    q_ref, c_ref, prm_ref, nr_ref, out_d_ref, out_i_ref, run_d, run_i,
    *, k, bn, n_steps, metric, qformat,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full(run_d.shape, jnp.inf, run_d.dtype)
        run_i[...] = jnp.full(run_i.shape, NEG_ONE, run_i.dtype)

    q = q_ref[...].astype(jnp.float32)                          # [1, D]
    c = c_ref[...].astype(jnp.float32)                          # [bn, D]
    if qformat == "int8":
        c = c * prm_ref[0, 0] + prm_ref[0, 1]                   # dequant on VPU
    # float16 codes ARE the (cast) rows: the f32 widening is the full decode
    if metric == "cosine":
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12)
        c = c * jax.lax.rsqrt(jnp.sum(c * c, -1, keepdims=True) + 1e-12)
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # f32: distance_bounds assume it
        preferred_element_type=jnp.float32,
    )                                                           # [1, bn] MXU
    if metric == "ip":
        d = -scores
    elif metric == "l2":
        d = (
            jnp.sum(q * q, -1)[:, None]
            + jnp.sum(c * c, -1)[None, :]
            - 2.0 * scores
        )
    else:  # cosine (pre-normalized above)
        d = 1.0 - scores

    gidx = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    valid = gidx < nr_ref[0, 0]                                 # per-group tail
    d = jnp.where(valid, d, jnp.inf)
    gidx = jnp.where(valid, gidx, NEG_ONE)  # groups may have < k valid rows

    md = jnp.concatenate([run_d[...], d], axis=1)               # [1, k+bn]
    mi = jnp.concatenate([run_i[...], gidx], axis=1)
    new_d, new_i = _merge_topk(md, mi, k)
    run_d[...] = new_d
    run_i[...] = new_i

    @pl.when(j == n_steps - 1)
    def _flush():
        out_d_ref[...] = run_d[...]
        # a group with < k valid rows pads with (inf, -1); _merge_topk's
        # exhausted-extraction re-reads position-0's id, so mask by value
        out_i_ref[...] = jnp.where(
            jnp.isinf(run_d[...]), NEG_ONE, run_i[...]
        ).astype(run_i.dtype)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "qformat", "bn", "interpret")
)
def _grouped_call(q, codes, prm, nr, k, metric, qformat, bn, interpret):
    G, N, D = codes.shape
    n_steps = N // bn
    if codes.dtype == jnp.float16:
        codes = codes.astype(jnp.float32)
    kern = functools.partial(
        _gkernel, k=k, bn=bn, n_steps=n_steps, metric=metric, qformat=qformat
    )
    out_d, out_i = pl.pallas_call(
        kern,
        grid=(G, n_steps),
        in_specs=[
            pl.BlockSpec((None, 1, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, bn, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, 2), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((G, 1, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, codes, prm, nr)
    return out_d[:, 0], out_i[:, 0]


def grouped_distance_topk_pallas(
    q,
    codes,
    scales,
    offsets,
    n_rows,
    k: int,
    metric: str = "l2",
    qformat: str = "int8",
    *,
    bn: int = 128,
    interpret: bool = False,
):
    """q [G, D], codes [G, N, D] (int8|f16), scales/offsets [G],
    n_rows [G] -> (dists [G, k] f32, idx [G, k] i32) ascending; rows past
    each group's n_rows come back as (inf, -1)."""
    q = np.asarray(q, np.float32)
    codes = np.asarray(codes)
    G, N, D = codes.shape
    G_pad = 1 << max(G - 1, 0).bit_length()
    N_pad = -(-max(N, 1) // bn) * bn
    if (G_pad, N_pad) != (G, N):
        padded = np.zeros((G_pad, N_pad, D), codes.dtype)
        padded[:G, :N] = codes
        codes = padded
    q3 = np.zeros((G_pad, 1, D), np.float32)
    q3[:G, 0] = q
    prm = np.zeros((G_pad, 1, 2), np.float32)
    prm[:G, 0, 0] = scales
    prm[:G, 0, 1] = offsets
    nr = np.zeros((G_pad, 1, 1), np.int32)  # pad groups have no valid rows
    nr[:G, 0, 0] = n_rows
    out_d, out_i = _grouped_call(q3, codes, prm, nr, k, metric, qformat, bn, interpret)
    return out_d[:G], out_i[:G]
