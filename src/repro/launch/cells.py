"""Cell construction: one lowerable (step_fn, abstract inputs, shardings)
per (architecture x input shape).

A Cell is everything the dry-run needs and nothing it must materialize:
  fn          the step function (train_step / prefill / decode / serve ...)
  args        ShapeDtypeStruct pytrees (weak-type-correct stand-ins)
  in_pspecs   PartitionSpec pytrees, same structure as args
  donate      argnums donated (state/caches) — buffer reuse in the compile
  meta        param counts / token counts for the roofline bench

``example_inputs`` materializes tiny concrete inputs for the SAME cell
definitions at reduced scale — smoke tests and the dry-run share one code
path, so what we smoke-test is what we lower.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import arch_shapes, get_arch
from repro.models import abstract_params, gnn, param_count, param_pspecs, recsys
from repro.models import transformer as T
from repro.models.base import init_params
from repro.models.retrieval_attention import ClusteredKVCache
from repro.optim import adamw, apply_updates, warmup_cosine

__all__ = ["Cell", "build_cell", "make_rules", "example_inputs", "lower_cell", "make_train_step"]

SDS = jax.ShapeDtypeStruct


@dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Callable
    args: tuple
    in_pspecs: tuple
    donate: tuple = ()
    out_pspecs: Any = None     # optional out_shardings pytree
    meta: dict = field(default_factory=dict)


def make_rules(mesh_axes) -> T.ShardingRules:
    batch = tuple(a for a in ("pod", "data") if a in mesh_axes)
    return T.ShardingRules(
        batch=batch, model="model" if "model" in mesh_axes else None
    )


# ------------------------------------------------------------- train step
def make_train_step(loss_fn, opt, *, microbatches: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    microbatches > 1: gradient accumulation via lax.scan over batch chunks —
    activation memory scales 1/n while the optimizer state is touched once.
    """

    def step(params, opt_state, batch):
        if microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:])

            chunks = jax.tree.map(split, batch)

            def acc_body(carry, chunk):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, chunk)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return (g_acc, l_acc + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), ms = jax.lax.scan(acc_body, (g0, 0.0), chunks)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss_sum / microbatches
            metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), ms)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, **metrics}

    return step


def _opt_for(cfg) -> Any:
    mdt = jnp.bfloat16 if getattr(cfg, "param_dtype", jnp.float32) == jnp.bfloat16 else jnp.float32
    return adamw(warmup_cosine(3e-4, 200, 10_000), moment_dtype=mdt)


def _abstract_opt(aparams, moment_dtype):
    m = jax.tree.map(lambda s: SDS(s.shape, moment_dtype), aparams)
    return {"mu": m, "nu": jax.tree.map(lambda s: SDS(s.shape, moment_dtype), aparams), "step": SDS((), jnp.int32)}


def _opt_pspecs(pparams):
    return {"mu": pparams, "nu": pparams, "step": P()}


# ------------------------------------------------------------------ LM
def _lm_cell(arch: str, cfg: T.LMConfig, shape_id: str, sh: dict, rules: T.ShardingRules) -> Cell:
    seq, batch = sh["seq"], sh["batch"]
    cfg = replace(cfg, max_seq=seq)
    specs = T.param_specs(cfg)
    aparams = abstract_params(specs)
    pparams = param_pspecs(specs)
    n_params = param_count(specs)
    Bax = rules.batch if rules.batch else None
    meta = {"n_params": n_params, "family": "lm", "cfg": cfg}
    # Megatron-style sequence parallelism for the residual stream: the
    # per-layer saved activations shard their seq dim over "model" (the
    # 123B x 88L checkpoint chain is 141 GiB/device without this).
    sp_rules = replace(rules, seq=rules.model) if rules.model else rules

    if sh["kind"] == "train":
        # Distribution policy (EXPERIMENTS.md §Perf iteration 2): dense LMs
        # on the single pod train pure-FSDP — batch over data x model (256-
        # way DP), params ZeRO-3 over both axes, ZERO activation
        # collectives. At 4096 tokens/device the parameter all-gather sits
        # at the ICI break-even (~3.9 kFLOP/byte), beating Megatron-SP whose
        # activation AG/RS dominated. MoE archs keep SP + expert-parallel
        # (replicating expert weights is never affordable); the multi-pod
        # mesh keeps TP=16 because GBS 256 < 512 chips.
        single_pod = "pod" not in (rules.batch or ()) and rules.model is not None
        if single_pod and cfg.moe is None and batch % 256 == 0:
            cfg = replace(cfg, fsdp_axis=("data", "model"), pure_fsdp=True, microbatches=1)
            t_rules = T.ShardingRules(batch=("data", "model"), model=None, seq=None)
            Bax_t = ("data", "model")
        else:
            t_rules = sp_rules
            Bax_t = Bax
        specs_t = T.param_specs(cfg)
        aparams_t = abstract_params(specs_t)
        pparams_t = param_pspecs(specs_t)
        meta["cfg"] = cfg
        opt = _opt_for(cfg)
        mdt = jnp.bfloat16 if cfg.param_dtype == jnp.bfloat16 else jnp.float32
        loss_fn = lambda p, b: T.lm_loss(p, b, cfg, t_rules)
        fn = make_train_step(loss_fn, opt, microbatches=cfg.microbatches)
        args = (aparams_t, _abstract_opt(aparams_t, mdt), {"tokens": SDS((batch, seq), jnp.int32)})
        pspecs = (pparams_t, _opt_pspecs(pparams_t), {"tokens": P(Bax_t, None)})
        out_ps = (pparams_t, _opt_pspecs(pparams_t), {"loss": P(), "xent": P(), "aux": P()})
        meta["tokens"] = batch * (seq - 1)
        return Cell(arch, shape_id, "train", fn, args, pspecs, donate=(0, 1),
                    out_pspecs=out_ps, meta=meta)

    if sh["kind"] == "prefill":
        fn = lambda params, tokens: T.prefill(params, tokens, cfg, sp_rules, max_seq=seq)
        args = (aparams, SDS((batch, seq), jnp.int32))
        pspecs = (pparams, P(Bax, None))
        out_ps = (
            P(Bax, None),                                       # last-pos logits
            T.KVCache(k=P(None, Bax, None, "model", None),
                      v=P(None, Bax, None, "model", None), pos=P()),
        )
        meta["tokens"] = batch * seq
        return Cell(arch, shape_id, "prefill", fn, args, pspecs, out_pspecs=out_ps, meta=meta)

    if sh["kind"] == "decode":
        cshape = (cfg.n_layers, batch, cfg.n_kv_heads, seq, cfg.d_head)
        acache = T.KVCache(k=SDS(cshape, cfg.dtype), v=SDS(cshape, cfg.dtype), pos=SDS((), jnp.int32))
        pcache = T.KVCache(
            k=P(None, Bax, None, "model", None),
            v=P(None, Bax, None, "model", None),
            pos=P(),
        )
        fn = lambda params, cache, tokens: T.decode_step(params, cache, tokens, cfg, rules)
        args = (aparams, acache, SDS((batch,), jnp.int32))
        pspecs = (pparams, pcache, P(Bax))
        out_ps = (P(Bax, None), pcache)
        meta["tokens"] = batch
        return Cell(arch, shape_id, "decode", fn, args, pspecs, donate=(1,),
                    out_pspecs=out_ps, meta=meta)

    if sh["kind"] == "retrieval_decode":
        cs = cfg.retrieval.cluster_size
        nC = -(-seq // cs)
        kv = (cfg.n_layers, batch, cfg.n_kv_heads, nC, cs, cfg.d_head)
        ce = (cfg.n_layers, batch, cfg.n_kv_heads, nC, cfg.d_head)
        seq_ax = (tuple(rules.batch) + ("model",)) if rules.batch else None
        acache = ClusteredKVCache(
            k=SDS(kv, cfg.dtype), v=SDS(kv, cfg.dtype),
            centroids=SDS(ce, jnp.float32), pos=SDS((), jnp.int32),
        )
        pcache = ClusteredKVCache(
            k=P(None, None, None, seq_ax, None, None),
            v=P(None, None, None, seq_ax, None, None),
            centroids=P(None, None, None, seq_ax, None),
            pos=P(),
        )
        fn = lambda params, cache, tokens: T.retrieval_decode_step(params, cache, tokens, cfg, rules)
        args = (aparams, acache, SDS((batch,), jnp.int32))
        pspecs = (pparams, pcache, P(None))
        out_ps = (P(None, None), pcache)
        meta["tokens"] = batch
        meta["n_clusters"] = nC
        return Cell(arch, shape_id, "retrieval_decode", fn, args, pspecs, donate=(1,),
                    out_pspecs=out_ps, meta=meta)

    raise ValueError(sh["kind"])


# ------------------------------------------------------------------ GNN
def _gnn_cell(arch: str, cfg0, shape_id: str, sh: dict, rules) -> Cell:
    Bax = rules.batch if rules.batch else None
    node_ax = (tuple(rules.batch) + ("model",)) if rules.batch else None

    if sh["kind"] == "full_graph":
        cfg = replace(cfg0, d_in=sh["d_feat"], n_classes=sh["n_classes"])
        specs = gnn.param_specs(cfg)
        aparams, pparams = abstract_params(specs), param_pspecs(specs)
        opt = adamw(3e-3)
        loss_fn = lambda p, b: gnn.gnn_loss_full(p, b, cfg)
        fn = make_train_step(loss_fn, opt)
        # pad node/edge counts to shard-divisible sizes (512 covers both
        # production meshes); pads carry edge_weight 0 / label_mask 0
        mult = 512
        N = -(-sh["n_nodes"] // mult) * mult
        E = -(-sh["n_edges"] // mult) * mult
        batch = {
            "feats": SDS((N, sh["d_feat"]), jnp.float32),
            "edge_src": SDS((E,), jnp.int32),
            "edge_dst": SDS((E,), jnp.int32),
            "edge_weight": SDS((E,), jnp.float32),
            "labels": SDS((N,), jnp.int32),
            "label_mask": SDS((N,), jnp.float32),
        }
        pbatch = {
            "feats": P(node_ax, None),
            "edge_src": P(node_ax),
            "edge_dst": P(node_ax),
            "edge_weight": P(node_ax),
            "labels": P(node_ax),
            "label_mask": P(node_ax),
        }
        args = (aparams, _abstract_opt(aparams, jnp.float32), batch)
        pspecs = (pparams, _opt_pspecs(pparams), pbatch)
        return Cell(arch, shape_id, "train", fn, args, pspecs, donate=(0, 1),
                    meta={"n_params": param_count(specs), "family": "gnn", "cfg": cfg})

    if sh["kind"] == "sampled":
        cfg = replace(cfg0, d_in=sh["d_feat"], n_classes=sh["n_classes"], fanouts=sh["fanouts"])
        specs = gnn.param_specs(cfg)
        aparams, pparams = abstract_params(specs), param_pspecs(specs)
        opt = adamw(3e-3)
        loss_fn = lambda p, b: gnn.gnn_loss_sampled(p, b, cfg)
        fn = make_train_step(loss_fn, opt)
        B, d = sh["batch_nodes"], sh["d_feat"]
        f1, f2 = sh["fanouts"]
        batch = {
            "hops": (
                SDS((B, f1, f2, d), jnp.float32),
                SDS((B, f1, d), jnp.float32),
                SDS((B, d), jnp.float32),
            ),
            "labels": SDS((B,), jnp.int32),
        }
        pbatch = {
            "hops": (P(Bax, None, None, None), P(Bax, None, None), P(Bax, None)),
            "labels": P(Bax),
        }
        args = (aparams, _abstract_opt(aparams, jnp.float32), batch)
        pspecs = (pparams, _opt_pspecs(pparams), pbatch)
        return Cell(arch, shape_id, "train", fn, args, pspecs, donate=(0, 1),
                    meta={"n_params": param_count(specs), "family": "gnn", "cfg": cfg})

    if sh["kind"] == "graphs":
        cfg = replace(cfg0, d_in=sh["d_feat"], n_classes=sh["n_classes"])
        specs = gnn.param_specs(cfg)
        aparams, pparams = abstract_params(specs), param_pspecs(specs)
        opt = adamw(3e-3)
        loss_fn = lambda p, b: gnn.gnn_loss_graphs(p, b, cfg)
        fn = make_train_step(loss_fn, opt)
        G, N, E = sh["batch"], sh["n_nodes"], sh["n_edges"]
        batch = {
            "feats": SDS((G, N, sh["d_feat"]), jnp.float32),
            "edge_src": SDS((G, E), jnp.int32),
            "edge_dst": SDS((G, E), jnp.int32),
            "node_mask": SDS((G, N), jnp.float32),
            "labels": SDS((G,), jnp.int32),
        }
        pbatch = {
            "feats": P(Bax, None, None),
            "edge_src": P(Bax, None),
            "edge_dst": P(Bax, None),
            "node_mask": P(Bax, None),
            "labels": P(Bax),
        }
        args = (aparams, _abstract_opt(aparams, jnp.float32), batch)
        pspecs = (pparams, _opt_pspecs(pparams), pbatch)
        return Cell(arch, shape_id, "train", fn, args, pspecs, donate=(0, 1),
                    meta={"n_params": param_count(specs), "family": "gnn", "cfg": cfg})

    raise ValueError(sh["kind"])


# --------------------------------------------------------------- recsys
def _recsys_batch_specs(cfg, batch: int, *, labeled: bool):
    n_plain = cfg.n_fields - cfg.seq_fields
    out = {"cat": SDS((batch, n_plain), jnp.int32)}
    if cfg.n_dense:
        out["dense"] = SDS((batch, cfg.n_dense), jnp.float32)
    if cfg.seq_len:
        out["seq"] = SDS((batch, cfg.seq_len, cfg.seq_fields), jnp.int32)
        out["seq_mask"] = SDS((batch, cfg.seq_len), jnp.float32)
        out["target"] = SDS((batch, cfg.seq_fields), jnp.int32)
    if labeled:
        out["label"] = SDS((batch,), jnp.float32)
    return out


def _recsys_batch_pspecs(batch_specs, Bax):
    # batch-1 cells (retrieval_cand) cannot shard their batch dim
    return {
        k: P(*(((Bax if v.shape[0] > 1 else None),) + (None,) * (len(v.shape) - 1)))
        for k, v in batch_specs.items()
    }


def _recsys_cell(arch: str, cfg, shape_id: str, sh: dict, rules) -> Cell:
    Bax = rules.batch if rules.batch else None
    specs = recsys.param_specs(cfg)
    aparams, pparams = abstract_params(specs), param_pspecs(specs)
    meta = {"n_params": param_count(specs), "family": "recsys", "cfg": cfg}

    if sh["kind"] == "train":
        opt = adamw(1e-3)
        loss_fn = lambda p, b: recsys.recsys_loss(p, b, cfg)
        fn = make_train_step(loss_fn, opt)
        bs = _recsys_batch_specs(cfg, sh["batch"], labeled=True)
        args = (aparams, _abstract_opt(aparams, jnp.float32), bs)
        pspecs = (pparams, _opt_pspecs(pparams), _recsys_batch_pspecs(bs, Bax))
        return Cell(arch, shape_id, "train", fn, args, pspecs, donate=(0, 1), meta=meta)

    if sh["kind"] == "serve":
        fn = lambda params, batch: jax.nn.sigmoid(recsys.forward(params, batch, cfg))
        bs = _recsys_batch_specs(cfg, sh["batch"], labeled=False)
        args = (aparams, bs)
        pspecs = (pparams, _recsys_batch_pspecs(bs, Bax))
        return Cell(arch, shape_id, "serve", fn, args, pspecs, meta=meta)

    if sh["kind"] == "retrieval":
        n_cand = sh["n_candidates"]
        n_pad = -(-n_cand // 512) * 512 if n_cand > 512 else n_cand
        cand_ax = (tuple(rules.batch) + ("model",)) if rules.batch else None
        bs = _recsys_batch_specs(cfg, sh["batch"], labeled=False)

        def fn(params, batch, cand_emb):
            q = recsys.user_tower(params, batch, cfg)
            s = q @ cand_emb.T                                  # [B, n_pad]
            s = jnp.where(jnp.arange(s.shape[-1]) < n_cand, s, -jnp.inf)
            return jax.lax.top_k(s, 100)

        args = (aparams, bs, SDS((n_pad, cfg.embed_dim), jnp.float32))
        pspecs = (pparams, _recsys_batch_pspecs(bs, Bax), P(cand_ax, None))
        meta["n_candidates"] = n_cand
        return Cell(arch, shape_id, "retrieval", fn, args, pspecs, meta=meta)

    raise ValueError(sh["kind"])


# ------------------------------------------------------------------ API
def build_cell(arch_id: str, shape_id: str, *, mesh_axes=("data", "model"), reduced: bool = False) -> Cell:
    family, cfg = get_arch(arch_id, reduced=reduced)
    sh = dict(arch_shapes(arch_id)[shape_id])
    rules = make_rules(mesh_axes) if mesh_axes else T.ShardingRules.null()
    if reduced:  # shrink the shape cell to smoke scale
        sh = _reduce_shape(family, sh)
    if family == "lm":
        return _lm_cell(arch_id, cfg, shape_id, sh, rules)
    if family == "gnn":
        return _gnn_cell(arch_id, cfg, shape_id, sh, rules)
    if family == "recsys":
        return _recsys_cell(arch_id, cfg, shape_id, sh, rules)
    raise ValueError(family)


def _reduce_shape(family: str, sh: dict) -> dict:
    sh = dict(sh)
    if family == "lm":
        sh["seq"] = min(sh["seq"], 64 if sh["kind"] != "retrieval_decode" else 128)
        sh["batch"] = min(sh["batch"], 4)
    elif family == "gnn":
        if sh["kind"] == "full_graph":
            sh.update(n_nodes=200, n_edges=800, d_feat=16, n_classes=5)
        elif sh["kind"] == "sampled":
            sh.update(batch_nodes=8, fanouts=(3, 2), d_feat=16, n_classes=5)
        else:
            sh.update(batch=4, n_nodes=10, n_edges=20, d_feat=16, n_classes=5)
    else:
        sh["batch"] = min(sh["batch"], 16)
        if sh["kind"] == "retrieval":
            sh["n_candidates"] = 1000
    return sh


def example_inputs(cell: Cell, seed: int = 0):
    """Materialize concrete inputs for a (reduced) cell: zeros/randints."""
    rng = np.random.default_rng(seed)
    cfg = cell.meta.get("cfg")

    def concrete(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", ""))) for p in path)
        if leaf.dtype in (jnp.int32, jnp.int64):
            if name.endswith("step") or name.endswith("pos"):
                return jnp.zeros(leaf.shape, leaf.dtype)
            return jnp.asarray(rng.integers(0, 2, size=leaf.shape), leaf.dtype)
        if "mask" in name or "weight" in name:
            return jnp.ones(leaf.shape, leaf.dtype)
        return jnp.zeros(leaf.shape, leaf.dtype)

    out = []
    for i, a in enumerate(cell.args):
        if i == 0 and isinstance(a, dict) and "cfg" in cell.meta:
            # params: properly initialized (not zeros) for numerically live runs
            fam = cell.meta["family"]
            if fam == "lm":
                out.append(init_params(T.param_specs(cfg), jax.random.key(seed)))
                continue
            if fam == "gnn":
                out.append(init_params(gnn.param_specs(cfg), jax.random.key(seed)))
                continue
            if fam == "recsys":
                out.append(init_params(recsys.param_specs(cfg), jax.random.key(seed)))
                continue
        out.append(jax.tree_util.tree_map_with_path(concrete, a))
    return tuple(out)


def lower_cell(cell: Cell, mesh):
    """jit + lower the cell on a mesh; returns the Lowered object."""
    from jax.sharding import NamedSharding

    is_ps = lambda x: isinstance(x, P)
    in_shardings = jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), cell.in_pspecs, is_leaf=is_ps
    )
    kw = {}
    if cell.out_pspecs is not None:
        kw["out_shardings"] = jax.tree.map(
            lambda ps: NamedSharding(mesh, ps) if isinstance(ps, P) else ps,
            cell.out_pspecs,
            is_leaf=lambda x: isinstance(x, P) or x is None,
        )
    jf = jax.jit(cell.fn, in_shardings=in_shardings, donate_argnums=cell.donate, **kw)
    with jax.sharding.set_mesh(mesh):
        return jf.lower(*cell.args)
