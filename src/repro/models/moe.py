"""Mixture-of-Experts FFN (top-1 routing, sort-based capacity dispatch).

Dispatch is the sort/scatter formulation (not the GShard [T, E, C] one-hot
einsum, which materializes T·E·C): tokens are argsorted by expert id,
positions within each expert group are computed from group starts, tokens
beyond capacity are dropped (mode='drop' scatter), experts run as a single
batched einsum over the [E, C, D] buffer, and outputs are scattered back.
Expert axis shards on "model" (expert parallelism); GSPMD inserts the
all-to-alls around the sharded scatter/gather.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = ["MoEConfig", "moe_ffn", "moe_ffn_ep"]


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int = 1                 # assigned archs use top-1 (Switch-style)
    d_ff: int = 8192
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


def moe_ffn(x, router_w, w_gate, w_up, w_down, cfg: MoEConfig):
    """x [T, D] -> ([T, D], aux_loss). Top-1 routing with capacity drop.

    router_w [D, E]; w_gate/w_up [E, D, F]; w_down [E, F, D].
    """
    T, D = x.shape
    E = cfg.n_experts
    C = max(1, int(cfg.capacity_factor * T / E))

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)                                       # [T]
    eidx = jnp.argmax(probs, axis=-1).astype(jnp.int32)                  # [T]

    # Switch load-balancing aux loss: E * sum_e f_e * P_e
    frac = jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=0)  # [E]
    mean_p = jnp.mean(probs, axis=0)                                     # [E]
    aux = E * jnp.sum(frac * mean_p) * cfg.aux_loss_weight

    order = jnp.argsort(eidx)                                            # [T]
    sorted_e = eidx[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))                   # [E]
    pos_in_e = jnp.arange(T, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)

    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[sorted_e, pos_in_e].set(x[order], mode="drop")

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    y = jnp.einsum("ecf,efd->ecd", g * u, w_down)                        # [E, C, D]

    kept = pos_in_e < C
    out_sorted = y[sorted_e, jnp.minimum(pos_in_e, C - 1)] * kept[:, None].astype(y.dtype)
    out = jnp.zeros((T, D), y.dtype).at[order].set(out_sorted)
    out = out * gate[:, None].astype(y.dtype)
    return out.astype(x.dtype), aux


def moe_ffn_ep(x, router_w, w_gate, w_up, w_down, cfg: MoEConfig, *, model_axis: str, batch_axes: tuple):
    """Expert-parallel MoE with ZERO dispatch all-to-all (shard_map).

    Precondition (Megatron-SP layers): x [T, D] is batch-sharded over
    ``batch_axes`` and REPLICATED over ``model_axis``; experts are sharded
    over ``model_axis``. Each model column therefore already holds every
    token — it routes/computes only the tokens whose top-1 expert it owns
    and contributes zeros otherwise, so the combine is ONE psum of [T, D]
    over the model axis. GSPMD's auto-partitioned scatter for the same
    dispatch all-reduces the [E, C, D] buffers (measured 10.5 TB/step/device
    on scout train_4k); this is the structural fix.
    """
    from jax.sharding import PartitionSpec as _P

    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    n_m = sizes[model_axis]
    E = cfg.n_experts
    assert E % n_m == 0, (E, n_m)
    E_loc = E // n_m
    bx = tuple(a for a in batch_axes if a in mesh.axis_names) or None

    def local(xb, rw, wg, wu, wd):
        T_loc, D = xb.shape
        C = max(1, int(cfg.capacity_factor * T_loc / E))
        m_idx = jax.lax.axis_index(model_axis)
        logits = xb.astype(jnp.float32) @ rw.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate = jnp.max(probs, axis=-1)
        eidx = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        lo = m_idx * E_loc
        mine = (eidx >= lo) & (eidx < lo + E_loc)
        e_loc = jnp.where(mine, eidx - lo, E_loc)          # E_loc = drop bucket
        order = jnp.argsort(e_loc)
        sorted_e = e_loc[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E_loc))
        pos = jnp.arange(T_loc, dtype=jnp.int32) - starts[
            jnp.minimum(sorted_e, E_loc - 1)
        ].astype(jnp.int32)
        buf = jnp.zeros((E_loc, C, D), xb.dtype)
        buf = buf.at[sorted_e, pos].set(xb[order], mode="drop")  # drops e_loc==E_loc too
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", g * u, wd)
        kept = (pos < C) & (sorted_e < E_loc) & (pos >= 0)
        out_sorted = y[jnp.minimum(sorted_e, E_loc - 1), jnp.clip(pos, 0, C - 1)]
        out_sorted = out_sorted * kept[:, None].astype(y.dtype)
        out = jnp.zeros((T_loc, D), y.dtype).at[order].set(out_sorted)
        out = out * gate[:, None].astype(y.dtype)
        return jax.lax.psum(out, model_axis)               # one owner per token

    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            _P(bx, None),
            _P(None, None),
            _P(model_axis, None, None),
            _P(model_axis, None, None),
            _P(model_axis, None, None),
        ),
        out_specs=_P(bx, None),
        check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)

    # aux load-balance loss on the (cheap, replicated) router pass
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    eidx = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0)) * cfg.aux_loss_weight
    return out.astype(x.dtype), aux
