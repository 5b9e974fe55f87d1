"""Attention dispatch for the LM family.

Three implementations, one math:
  * ``full``    — plain einsum softmax attention (tiny smoke configs);
  * ``chunked`` — lax.scan over kv blocks with the online-softmax
                  recurrence; differentiable; with jax.checkpoint on the
                  body its live memory is O(Sq·chunk) instead of O(Sq·Skv).
                  This is the TRAINING path for the big configs.
  * ``flash``   — the Pallas kernel (kernels/flash_attention), serving path.

All are GQA-aware ([B, Hq, Sq, d] queries vs [B, Hkv, Skv, d] kv).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas, mha_ref

__all__ = ["attention"]


def _chunked(q, k, v, *, causal, scale, chunk, kv_lens=None, remat=True):
    """Exact attention, scanned over QUERY blocks, flat-head layout.

    Why q-blocks and not the kv-block online-softmax recurrence: under
    ``lax.scan`` autodiff the kv formulation must save its carry — the full
    [B, H, Sq, d] accumulator — once per kv chunk (O(Sq·Skv·d / chunk)
    residual memory; this was a measured 410 GiB/device on the 123B train
    cell). The q formulation has NO carry: each block's softmax over the
    whole kv is exact and independent, the checkpointed body recomputes its
    [cq, Skv] score block in the backward pass, and the only saved tensors
    are the per-block inputs/outputs (O(Sq·d)).

    Why flat heads + bf16 repeat instead of a [B, Hkv, group, S, d] view:
    Hkv (4..8) and group (3..12) do not divide a 16-wide model axis, so
    GSPMD replicates the 5D layout across it; the flat Hq axis (24..96)
    shards evenly. The repeat is in the storage dtype and head-sharded —
    measured 33->19 GiB/device on the 123B train cell.
    """
    B, Hq, Sq, d = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    cq = min(chunk, Sq)
    n_chunks = -(-Sq // cq)
    pad = n_chunks * cq - Sq
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q
    qc = qp.reshape(B, Hq, n_chunks, cq, d).transpose(2, 0, 1, 3, 4)
    # GQA expansion: storage-dtype repeat on the flat (shardable) head axis;
    # f32 accumulation comes from preferred_element_type, never an f32 copy.
    ke = jnp.repeat(k, group, axis=1) if group > 1 else k    # [B,Hq,Skv,d]
    ve = jnp.repeat(v, group, axis=1) if group > 1 else v
    kv_idx = jnp.arange(Skv)
    end = kv_lens[:, None] if kv_lens is not None else jnp.full((B, 1), Skv)

    def body(_, xs):
        qb, j = xs                                   # qb [B,Hq,cq,d]
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qb, ke, preferred_element_type=jnp.float32
        ) * scale                                    # [B,Hq,cq,Skv] f32
        mask = kv_idx[None, None, :] < end[:, None, :]       # [B,1,Skv]
        if causal:
            q_idx = j * cq + jnp.arange(cq)
            mask = mask & (
                kv_idx[None, None, :] <= (q_idx[None, :, None] + (end[:, :, None] - Sq))
            )
        s = jnp.where(mask[:, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        p = jnp.where(mask[:, None], p, 0.0)
        denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum(
            "bhqk,bhkd->bhqd", (p / denom).astype(ve.dtype), ve,
            preferred_element_type=jnp.float32,
        )
        return None, o

    if remat:
        body = jax.checkpoint(body)
    _, oc = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)))
    out = oc.transpose(1, 2, 0, 3, 4).reshape(B, Hq, n_chunks * cq, d)
    return out[:, :, :Sq]


def flash_decode_sharded(q, k, v, kv_lens, *, model_axis: str, scale: float | None = None):
    """Decode attention with the KV cache seq-sharded over ``model_axis``.

    Explicit flash-decoding via shard_map: each shard computes its partial
    (m, l, acc) over its local cache slice, then a 3-scalar-tree psum/pmax
    combines them — the ONLY cross-device traffic is O(B·Hq·d), never the
    cache. (GSPMD's auto choice for the same einsum all-gathers the cache:
    measured 8.6 GiB/device of gathered bf16 cache on the 123B decode cell.)

    q [B, Hq, 1, d]; k/v [B, Hkv, Skv, d] sharded (B: data, Skv: model).
    """
    from jax.sharding import PartitionSpec as _P

    B, Hq, _, d = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    mesh = jax.sharding.get_abstract_mesh()
    batch_ax = None
    # infer the batch axis from current mesh axes (pod+data when present)
    bx = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_ax = bx if bx else None
    n_shards = 1
    for a in (model_axis,):
        n_shards *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
    S_loc = Skv // n_shards

    def local(qb, kb, vb, lens):
        # kb/vb [Bl, Hkv, S_loc, d]; qb [Bl, Hq, 1, d]; lens [Bl]
        off = jax.lax.axis_index(model_axis) * S_loc
        ke = jnp.repeat(kb, group, axis=1) if group > 1 else kb
        ve = jnp.repeat(vb, group, axis=1) if group > 1 else vb
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, ke, preferred_element_type=jnp.float32) * scale
        idx = off + jnp.arange(S_loc)
        mask = idx[None, None, None, :] < lens[:, None, None, None]
        s = jnp.where(mask, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)                      # [B,H,1,1]
        p = jnp.where(mask, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(ve.dtype), ve, preferred_element_type=jnp.float32)
        # combine partial softmaxes across cache shards
        m_g = jax.lax.pmax(m, model_axis)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - jnp.where(jnp.isfinite(m_g), m_g, 0.0)), 0.0)
        l_g = jax.lax.psum(l * corr, model_axis)
        acc_g = jax.lax.psum(acc * corr, model_axis)
        return acc_g / jnp.maximum(l_g, 1e-30)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            _P(batch_ax, None, None, None),
            _P(batch_ax, None, model_axis, None),
            _P(batch_ax, None, model_axis, None),
            _P(batch_ax),
        ),
        out_specs=_P(batch_ax, None, None, None),
        check_vma=False,
    )(q, k, v, kv_lens)


def attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    kv_lens=None,
    scale: float | None = None,
    impl: str = "chunked",
    chunk: int = 1024,
    remat: bool = True,
):
    """Unified attention. Returns [B, Hq, Sq, d] in float32."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    if impl == "full":
        return mha_ref(q, k, v, causal=causal, kv_lens=kv_lens, scale=scale)
    if impl == "chunked":
        return _chunked(
            q, k, v, causal=causal, scale=scale, chunk=chunk, kv_lens=kv_lens, remat=remat
        )
    if impl == "flash":
        return flash_attention_pallas(q, k, v, kv_lens=kv_lens, causal=causal, scale=scale)
    if impl == "flash_interpret":
        return flash_attention_pallas(
            q, k, v, kv_lens=kv_lens, causal=causal, scale=scale, interpret=True
        )
    raise ValueError(f"unknown attention impl {impl!r}")
