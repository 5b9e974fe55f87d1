"""Pallas TPU kernel: online-softmax (flash) attention forward, GQA-aware.

Tiling:
  * grid = (B, Hq, Sq/bq, Skv/bk); the kv axis is sequential ("arbitrary"),
    carrying (m, l, acc) in VMEM scratch — the classic flash recurrence.
  * q block [bq, d], k/v blocks [bk, d] in VMEM; scores on the MXU with f32
    accumulation. bq = bk = 128 by default (MXU-aligned).
  * GQA: query head h reads kv head h // (Hq // Hkv) via the BlockSpec
    index maps — no repeat/materialization of kv heads.
  * causal masking aligns the LAST query with the last valid kv position
    (works for both prefill Sq == Skv and chunked/decode Sq < Skv);
    per-batch valid kv length arrives as an SMEM scalar block.
  * fully-masked kv blocks are skipped with pl.when (causal wedge skip).

VMEM at defaults (d=128): q/k/v blocks 64 KB each, acc 64 KB — ~0.3 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


def _kernel(
    len_ref,  # SMEM [1] int32: valid kv length for this batch row
    q_ref, k_ref, v_ref,  # VMEM blocks
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    causal: bool,
    scale: float,
    bq: int,
    bk: int,
    sq: int,
    skv: int,
    n_kv_steps: int,
):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    i = pl.program_id(2)
    kv_len = len_ref[0]
    q_end_offset = kv_len - sq  # causal alignment shift

    # skip kv blocks entirely in the causal future or past the valid length
    q_hi = (i + 1) * bq - 1 + q_end_offset
    block_live = (j * bk <= q_hi) if causal else (j * bk < kv_len)

    @pl.when(block_live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                    # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)                    # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                      # [bq, bk]
        kv_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kv_idx < kv_len
        if causal:
            q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask &= kv_idx <= (q_idx + q_end_offset)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                                    # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # guard rows with no live keys yet (m == -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(j == n_kv_steps - 1)
    def _flush():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bk", "interpret", "scale"),
)
def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kv_lens: jnp.ndarray | None = None,
    causal: bool = True,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """q [B,Hq,Sq,d]; k,v [B,Hkv,Skv,d] -> [B,Hq,Sq,d] (f32).

    kv_lens [B] int32: per-sequence valid kv length (default: full Skv).
    """
    B, Hq, Sq, d = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    if kv_lens is None:
        kv_lens = jnp.full((B,), Skv, jnp.int32)
    bq_ = min(bq, Sq)
    bk_ = min(bk, Skv)
    Sq_pad = -(-Sq // bq_) * bq_
    Skv_pad = -(-Skv // bk_) * bk_
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_pad - Sq), (0, 0)))
    if Skv_pad != Skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Skv_pad - Skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Skv_pad - Skv), (0, 0)))
    n_kv_steps = Skv_pad // bk_
    kern = functools.partial(
        _kernel,
        causal=causal,
        scale=scale,
        bq=bq_,
        bk=bk_,
        sq=Sq,
        skv=Skv,
        n_kv_steps=n_kv_steps,
    )
    grid = (B, Hq, Sq_pad // bq_, n_kv_steps)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, i, j: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq_, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk_, d), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk_, d), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_pad, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(kv_lens.astype(jnp.int32), q, k, v)
    return out[:, :, :Sq]
