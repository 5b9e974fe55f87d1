"""Pallas TPU kernel: fused distance computation + running top-k.

The eCP-FS hot spot (DESIGN.md §7): score a query block against a large
candidate set (cluster leaders, leaf items, recsys candidates, KV-cluster
centroids) and keep only the k best — without ever materializing the [B, N]
distance matrix in HBM.

Layout / tiling:
  * grid = (B/bq, N/bn); the candidate axis is ``arbitrary`` (sequential) so
    a VMEM scratch accumulator carries the running top-k across blocks.
  * q block [bq, D] and c block [bn, D] live in VMEM; the MXU computes
    q @ cᵀ with f32 accumulation (preferred_element_type).
  * bq/bn default 128 — MXU-aligned (multiples of 128 on both matmul dims).
  * selection is a k-step masked-argmin extraction over the concatenated
    [bq, k + bn] candidates — pure VPU ops (min/compare/iota); no
    sort/top_k, and no cumsum, which Mosaic does not lower.
  * the matmul runs at ``Precision.HIGHEST`` (f32 contraction): the
    quantized scan's pruning bounds (core/quant.py) assume f32-exact
    distances to the decoded rows, which a bf16 pass would break.

VMEM budget at defaults (D=1152, bq=bn=128, k=128):
  q 128·1152·4 = 576 KB, c 576 KB, scores 64 KB, scratch 2·64 KB ≈ 1.4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_ONE = -1


def _merge_topk(md, mi, k):
    """k-step extraction of the k smallest (value, id) pairs.

    md: [bq, M] distances, mi: [bq, M] int32 ids. Ties resolved to the
    first (lowest position ⇒ lowest candidate index): the smallest
    position holding the minimum, found as a masked min over an iota.
    Returns ([bq, k], [bq, k]) ascending.
    """
    pos = jax.lax.broadcasted_iota(jnp.int32, md.shape, 1)
    n_pos = md.shape[1]
    out_d, out_i = [], []
    for _ in range(k):
        m = jnp.min(md, axis=1, keepdims=True)                  # [bq, 1]
        first_pos = jnp.min(jnp.where(md == m, pos, n_pos), axis=1, keepdims=True)
        first = pos == first_pos
        sel_i = jnp.sum(jnp.where(first, mi, 0), axis=1)        # unique hit
        out_d.append(m[:, 0])
        out_i.append(sel_i)
        md = jnp.where(first, jnp.inf, md)
    return jnp.stack(out_d, axis=1), jnp.stack(out_i, axis=1).astype(jnp.int32)


def _kernel(q_ref, c_ref, out_d_ref, out_i_ref, run_d, run_i, *, k, bn, n_total, n_steps, metric):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full(run_d.shape, jnp.inf, run_d.dtype)
        run_i[...] = jnp.full(run_i.shape, NEG_ONE, run_i.dtype)

    q = q_ref[...].astype(jnp.float32)                          # [bq, D]
    c = c_ref[...].astype(jnp.float32)                          # [bn, D]
    if metric == "cosine":
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12)
        c = c * jax.lax.rsqrt(jnp.sum(c * c, -1, keepdims=True) + 1e-12)
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                           # [bq, bn] MXU
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

    bq = d.shape[0]
    gidx = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    d = jnp.where(gidx < n_total, d, jnp.inf)                   # tail mask

    md = jnp.concatenate([run_d[...], d], axis=1)               # [bq, k+bn]
    mi = jnp.concatenate([run_i[...], gidx], axis=1)
    new_d, new_i = _merge_topk(md, mi, k)
    run_d[...] = new_d
    run_i[...] = new_i

    @pl.when(j == n_steps - 1)
    def _flush():
        out_d_ref[...] = run_d[...]
        out_i_ref[...] = run_i[...]


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "bq", "bn", "interpret")
)
def distance_topk_pallas(
    q: jnp.ndarray,
    c: jnp.ndarray,
    k: int,
    metric: str = "l2",
    *,
    bq: int = 128,
    bn: int = 128,
    interpret: bool = False,
):
    """Fused top-k nearest candidates. q [B, D], c [N, D] -> ([B,k],[B,k])."""
    B, D = q.shape
    N = c.shape[0]
    B_pad = -(-B // bq) * bq
    N_pad = -(-N // bn) * bn
    if B_pad != B:
        q = jnp.pad(q, ((0, B_pad - B), (0, 0)))
    if N_pad != N:
        c = jnp.pad(c, ((0, N_pad - N), (0, 0)))
    n_steps = N_pad // bn
    kern = functools.partial(
        _kernel, k=k, bn=bn, n_total=N, n_steps=n_steps, metric=metric
    )
    out_d, out_i = pl.pallas_call(
        kern,
        grid=(B_pad // bq, n_steps),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((B_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, c)
    return out_d[:B], out_i[:B]
