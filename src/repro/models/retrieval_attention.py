"""eCP retrieval attention — the paper's index running inside the model.

For long-context decode (seq 500k+) full attention is infeasible; the KV
cache is instead organized exactly like an eCP leaf level: fixed-size
clusters of ``cs`` consecutive tokens, each with a centroid (running mean of
its keys — the "cluster leader"). A decode step:

  1. scores the query against all cluster centroids (the paper's index
     traversal; with n_clusters ~ 1024 this is the L=1 case — an L=2
     centroid tree is supported for >100k clusters),
  2. selects the top-b clusters per kv head (search expansion b, paper §3),
  3. gathers those clusters' K/V blocks and runs exact attention over them,
     plus the current (partial) cluster — the paper's "incremental" bias
     toward recent context.

Complexity per step: O(nC·d + b·cs·d) instead of O(S·d): at S=524288,
cs=512, b=32 that is 1024 + 16384 token scores vs 524288 — a 32× cut.

The clustered cache is a pytree shardable over the sequence/cluster axis
("data" axis at batch=1 — sequence parallelism), which is how the 500k cell
distributes: centroid scoring is local, the top-b reduce is a tiny
all-gather, gathers stay shard-local in expectation.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = ["ClusteredKVCache", "RetrievalAttnConfig", "init_clustered_cache", "retrieval_decode_attention", "retrieval_decode_attention_sharded", "clustered_cache_update"]


@dataclass(frozen=True)
class RetrievalAttnConfig:
    cluster_size: int = 512     # cs: tokens per KV cluster (eCP cluster cap)
    top_clusters: int = 32      # b: search expansion


@jax.tree_util.register_pytree_node_class
@dataclass
class ClusteredKVCache:
    k: jnp.ndarray          # [L, B, Hkv, nC, cs, d]
    v: jnp.ndarray          # [L, B, Hkv, nC, cs, d]
    centroids: jnp.ndarray  # [L, B, Hkv, nC, d] running mean of keys
    pos: jnp.ndarray        # [] int32 — tokens written so far

    def tree_flatten(self):
        return (self.k, self.v, self.centroids, self.pos), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_clustered_cache(n_layers, batch, n_kv, max_seq, cs, d, dtype=jnp.bfloat16):
    nC = -(-max_seq // cs)
    return ClusteredKVCache(
        k=jnp.zeros((n_layers, batch, n_kv, nC, cs, d), dtype),
        v=jnp.zeros((n_layers, batch, n_kv, nC, cs, d), dtype),
        centroids=jnp.zeros((n_layers, batch, n_kv, nC, d), jnp.float32),
        pos=jnp.zeros((), jnp.int32),
    )


def clustered_cache_update(layer_k, layer_v, layer_cent, k_new, v_new, pos, cs):
    """Write one token's k/v into its cluster; update the centroid mean.

    layer_k/v [B, Hkv, nC, cs, d]; k_new/v_new [B, Hkv, d]; pos scalar.
    """
    cid = pos // cs
    off = pos % cs
    layer_k = jax.lax.dynamic_update_slice(
        layer_k, k_new[:, :, None, None, :].astype(layer_k.dtype), (0, 0, cid, off, 0)
    )
    layer_v = jax.lax.dynamic_update_slice(
        layer_v, v_new[:, :, None, None, :].astype(layer_v.dtype), (0, 0, cid, off, 0)
    )
    old_c = jax.lax.dynamic_slice_in_dim(layer_cent, cid, 1, axis=2)[:, :, 0]  # [B,Hkv,d]
    n = (off + 1).astype(jnp.float32)
    new_c = old_c + (k_new.astype(jnp.float32) - old_c) / n
    layer_cent = jax.lax.dynamic_update_slice(
        layer_cent, new_c[:, :, None, :], (0, 0, cid, 0)
    )
    return layer_k, layer_v, layer_cent


def retrieval_decode_attention(
    q, layer_k, layer_v, layer_cent, pos, *, cs: int, top_b: int, scale: float | None = None
):
    """One decode step of eCP retrieval attention.

    q [B, Hq, d] (single token); layer_k/v [B, Hkv, nC, cs, d];
    layer_cent [B, Hkv, nC, d]; pos scalar int32 (tokens already cached,
    INCLUDING the current token already written). Returns [B, Hq, d] f32.
    """
    B, Hq, d = q.shape
    Hkv, nC = layer_k.shape[1], layer_k.shape[2]
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(B, Hkv, group, d)

    # 1) index traversal: score centroids (inner-product metric, as the
    #    softmax numerator is monotone in <q, k>); mean over the query group
    cur = (pos - 1) // cs                                   # current cluster id
    cent_scores = jnp.einsum("bhgd,bhnd->bhgn", qg, layer_cent).mean(2)  # [B,Hkv,nC]
    full_mask = jnp.arange(nC)[None, None, :] < cur          # only complete clusters
    cent_scores = jnp.where(full_mask, cent_scores, -jnp.inf)

    # 2) search expansion: top-b complete clusters + the current one
    b = min(top_b, nC)
    _, top_idx = jax.lax.top_k(cent_scores, b)               # [B, Hkv, b]
    sel = jnp.concatenate([top_idx, jnp.broadcast_to(cur, (B, Hkv, 1))], axis=-1)  # [B,Hkv,b+1]

    # 3) gather + exact attention over the selected clusters
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(Hkv)[None, :, None]
    ks = layer_k[bi, hi, sel]                                # [B, Hkv, b+1, cs, d]
    vs = layer_v[bi, hi, sel]
    # token validity: cluster j is full (cs) if j < cur, partial if j == cur
    tok_idx = sel[..., None] * cs + jnp.arange(cs)[None, None, None, :]  # [B,Hkv,b+1,cs]
    valid = (tok_idx < pos) & (sel[..., None] >= 0) & jnp.isfinite(
        jnp.concatenate([jnp.take_along_axis(cent_scores, top_idx, -1),
                         jnp.zeros((B, Hkv, 1))], axis=-1)
    )[..., None]
    s = jnp.einsum("bhgd,bhncd->bhgnc", qg, ks.astype(jnp.float32))      # [B,Hkv,g,b+1,cs]
    s = jnp.where(valid[:, :, None], s, -jnp.inf)
    sf = s.reshape(B, Hkv, group, -1)
    m = jnp.max(sf, axis=-1, keepdims=True)
    p = jnp.exp(sf - jnp.where(jnp.isfinite(m), m, 0.0))
    p = jnp.where(jnp.isfinite(sf), p, 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    p = (p / denom).reshape(B, Hkv, group, b + 1, cs)
    out = jnp.einsum("bhgnc,bhncd->bhgd", p, vs.astype(jnp.float32))
    return out.reshape(B, Hq, d)


def retrieval_update_and_attend_sharded(
    q, layer_k, layer_v, layer_cent, k_new, v_new, pos, *, cs: int, top_b: int, seq_axes: tuple, scale: float | None = None
):
    """Fused sharded cache update + retrieval attention (§Perf iteration 4).

    Writing one token into the nC-sharded clustered cache through GSPMD
    costs a per-layer gather of the centroid/cluster arrays (measured
    0.13 GB/step across 32 layers — most of the remaining collective time
    after iteration 1). Fused into the same shard_map, only the shard that
    OWNS the current cluster applies the dynamic-update-slice; everything
    stays local. k_new/v_new [B, Hkv, d] are replicated (tiny).

    Returns (attn_out [B,Hq,d], layer_k, layer_v, layer_cent) with the
    cache updated at ``pos`` and attention evaluated at ``pos + 1``.
    """
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    n_sh = 1
    for a in seq_axes:
        n_sh *= sizes[a]
    B, Hq, d = q.shape
    Hkv, nC = layer_k.shape[1], layer_k.shape[2]
    nC_loc = nC // n_sh
    if scale is None:
        scale = 1.0 / (d**0.5)
    from jax.sharding import PartitionSpec as _P

    def local(qb, kb, vb, cb, knb, vnb, posb):
        off = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            off = off * sizes[a] + jax.lax.axis_index(a)
        off = off * nC_loc
        # ---- owner-local cache write
        cid = posb // cs
        tok_off = posb % cs
        mine = (cid >= off) & (cid < off + nC_loc)
        lid = jnp.clip(cid - off, 0, nC_loc - 1)
        k_upd = jax.lax.dynamic_update_slice(
            kb, knb[:, :, None, None, :].astype(kb.dtype), (0, 0, lid, tok_off, 0)
        )
        v_upd = jax.lax.dynamic_update_slice(
            vb, vnb[:, :, None, None, :].astype(vb.dtype), (0, 0, lid, tok_off, 0)
        )
        old_c = jax.lax.dynamic_slice_in_dim(cb, lid, 1, axis=2)[:, :, 0]
        new_c = old_c + (knb.astype(jnp.float32) - old_c) / (tok_off + 1).astype(jnp.float32)
        c_upd = jax.lax.dynamic_update_slice(cb, new_c[:, :, None, :], (0, 0, lid, 0))
        kb = jnp.where(mine, k_upd, kb)
        vb = jnp.where(mine, v_upd, vb)
        cb = jnp.where(mine, c_upd, cb)
        # ---- the iteration-1 sharded search/attend at pos+1
        out = _local_retrieval_attend(
            qb, kb, vb, cb, posb + 1, off=off, cs=cs, top_b=top_b,
            seq_axes=seq_axes, scale=scale, nC_loc=nC_loc, B=B, Hq=Hq, Hkv=Hkv,
        )
        return out, kb, vb, cb

    seq_spec = tuple(seq_axes)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            _P(None, None, None),
            _P(None, None, seq_spec, None, None),
            _P(None, None, seq_spec, None, None),
            _P(None, None, seq_spec, None),
            _P(None, None, None),
            _P(None, None, None),
            _P(),
        ),
        out_specs=(
            _P(None, None, None),
            _P(None, None, seq_spec, None, None),
            _P(None, None, seq_spec, None, None),
            _P(None, None, seq_spec, None),
        ),
        check_vma=False,
    )(q, layer_k, layer_v, layer_cent, k_new, v_new, pos)


def _local_retrieval_attend(qb, kb, vb, cb, posb, *, off, cs, top_b, seq_axes, scale, nC_loc, B, Hq, Hkv):
    """Shard-local body shared by the sharded retrieval attention entry
    points: local centroid scoring -> global-threshold selection -> masked
    partial attention -> flash-style psum combine."""
    group = Hq // Hkv
    qg = (qb.astype(jnp.float32) * scale).reshape(B, Hkv, group, qb.shape[-1])
    cent_s = jnp.einsum("bhgd,bhnd->bhgn", qg, cb).mean(2)
    cur = (posb - 1) // cs
    gidx = off + jnp.arange(nC_loc)
    full = gidx[None, None, :] < cur
    cent_m = jnp.where(full, cent_s, -jnp.inf)
    b_loc = min(top_b, nC_loc)
    loc_top, _ = jax.lax.top_k(cent_m, b_loc)
    allc = jax.lax.all_gather(loc_top, seq_axes)
    flat = jnp.moveaxis(allc, 0, -2).reshape(B, Hkv, -1)
    kk = min(top_b, flat.shape[-1])
    kth = jax.lax.top_k(flat, kk)[0][..., -1]
    sel = (cent_m >= kth[..., None]) & full
    sel = sel | (gidx[None, None, :] == cur)
    s = jnp.einsum("bhgd,bhncd->bhgnc", qg.astype(kb.dtype), kb, preferred_element_type=jnp.float32)
    tok = gidx[:, None] * cs + jnp.arange(cs)[None, :]
    valid = sel[:, :, None, :, None] & (tok < posb)[None, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    sf = s.reshape(B, Hkv, group, -1)
    m_loc = jnp.max(sf, axis=-1, keepdims=True)
    safe = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
    p = jnp.where(jnp.isfinite(sf), jnp.exp(sf - safe), 0.0)
    l_loc = jnp.sum(p, axis=-1, keepdims=True)
    acc_loc = jnp.einsum(
        "bhgnc,bhncd->bhgd",
        p.reshape(B, Hkv, group, nC_loc, cs).astype(vb.dtype), vb,
        preferred_element_type=jnp.float32,
    )
    m_g = jax.lax.pmax(m_loc, seq_axes)
    corr = jnp.where(jnp.isfinite(m_loc), jnp.exp(m_loc - jnp.where(jnp.isfinite(m_g), m_g, 0.0)), 0.0)
    l_g = jax.lax.psum(l_loc * corr, seq_axes)
    acc_g = jax.lax.psum(acc_loc * corr[..., 0][..., None], seq_axes)
    out = acc_g / jnp.maximum(l_g[..., 0][..., None], 1e-30)
    return out.reshape(B, Hq, qb.shape[-1])


def retrieval_decode_attention_sharded(
    q, layer_k, layer_v, layer_cent, pos, *, cs: int, top_b: int, seq_axes: tuple, scale: float | None = None
):
    """Sequence-parallel eCP retrieval attention: the clusters NEVER move.

    The clustered cache shards its cluster axis over ``seq_axes``. GSPMD's
    auto-partitioning of the gather-then-attend formulation all-reduces the
    gathered [B,Hkv,b+1,cs,d] cluster contents (measured 8.86 GB x L per
    decode step). Here each shard instead:
      1. scores ITS centroids (index traversal stays local),
      2. contributes its local top-b scores to a tiny all-gather
         ([B,Hkv,b_loc] f32) from which the global b-th best score is the
         selection threshold (ties may admit a few extra clusters —
         same-spirit approximation as MoE capacity),
      3. runs masked partial attention over its local clusters only, and
      4. combines with the flash-decoding (m, l, acc) psum — O(B·Hq·d).
    Wire bytes per layer: O(n_sh·b_loc + B·Hq·d) ~ 100 KB vs 8.86 GB.
    """
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    n_sh = 1
    for a in seq_axes:
        n_sh *= sizes[a]
    B, Hq, d = q.shape
    Hkv, nC = layer_k.shape[1], layer_k.shape[2]
    nC_loc = nC // n_sh
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (d**0.5)
    from jax.sharding import PartitionSpec as _P

    def local(qb, kb, vb, cb, posb):
        off = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            off = off * sizes[a] + jax.lax.axis_index(a)
        off = off * nC_loc
        return _local_retrieval_attend(
            qb, kb, vb, cb, posb, off=off, cs=cs, top_b=top_b,
            seq_axes=seq_axes, scale=scale, nC_loc=nC_loc, B=B, Hq=Hq, Hkv=Hkv,
        )

    seq_spec = tuple(seq_axes)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            _P(None, None, None),
            _P(None, None, seq_spec, None, None),
            _P(None, None, seq_spec, None, None),
            _P(None, None, seq_spec, None),
            _P(),
        ),
        out_specs=_P(None, None, None),
        check_vma=False,
    )(q, layer_k, layer_v, layer_cent, pos)
