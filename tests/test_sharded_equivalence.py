"""Distributed-path numerical equivalence (the §Perf optimizations).

These run in a SUBPROCESS with 8 forced host devices (the main pytest
process must stay single-device), asserting that the optimized sharded
implementations match the single-logic references:

  * pure-FSDP / Megatron-SP LM train loss+grads  == reference
  * token-replicated expert-parallel MoE          == global dispatch (no-drop)
  * sequence-parallel eCP retrieval attention     == reference gather version
"""
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, r"%SRC%")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import transformer as T
from repro.models.base import init_params, param_pspecs
from repro.models.moe import MoEConfig
from repro.models.retrieval_attention import (
    retrieval_decode_attention, retrieval_decode_attention_sharded)

mesh = jax.make_mesh((2, 4), ("data", "model"))

def put(params, pspecs):
    return jax.device_put(params, jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), pspecs,
        is_leaf=lambda x: isinstance(x, P)))

# --- 1) dense SP train path
cfg = T.LMConfig(name="t", n_layers=2, d_model=32, n_heads=8, n_kv_heads=2,
                 d_ff=64, vocab=64, d_head=8, max_seq=32, dtype=jnp.float32,
                 attn_chunk=16)
specs = T.param_specs(cfg)
params = init_params(specs, jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (4, 32), 0, 64)
ref, _ = T.lm_loss(params, {"tokens": toks}, cfg)
rules = T.ShardingRules(batch=("data",), model="model", seq="model")
with jax.sharding.set_mesh(mesh):
    pp = put(params, param_pspecs(specs))
    tt = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    sp, _ = jax.jit(lambda p, b: T.lm_loss(p, b, cfg, rules))(pp, {"tokens": tt})
    g_ref = jax.grad(lambda p: T.lm_loss(p, {"tokens": toks}, cfg)[0])(params)
    g_sp = jax.jit(jax.grad(lambda p: T.lm_loss(p, {"tokens": tt}, cfg, rules)[0]))(pp)
gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in
           zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sp)))
assert abs(float(ref - sp)) < 1e-5, ("sp loss", float(ref), float(sp))
assert gerr < 1e-5, ("sp grads", gerr)

# --- 2) EP MoE under no-drop capacity
cfg = T.LMConfig(name="t", n_layers=2, d_model=32, n_heads=8, n_kv_heads=2,
                 d_ff=64, vocab=64, d_head=8, max_seq=32, dtype=jnp.float32,
                 moe=MoEConfig(n_experts=8, d_ff=64, capacity_factor=16.0),
                 attn_chunk=16)
specs = T.param_specs(cfg)
params = init_params(specs, jax.random.key(0))
ref, _ = T.lm_loss(params, {"tokens": toks}, cfg)
with jax.sharding.set_mesh(mesh):
    pp = put(params, param_pspecs(specs))
    tt = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    sp, _ = jax.jit(lambda p, b: T.lm_loss(p, b, cfg, rules))(pp, {"tokens": tt})
assert abs(float(ref - sp)) < 1e-5, ("ep loss", float(ref), float(sp))

# --- 3) sharded retrieval attention
rng = np.random.default_rng(0)
B, Hq, Hkv, nC, cs, d = 1, 8, 2, 16, 8, 32
q = jnp.asarray(rng.normal(size=(B, Hq, d)), jnp.float32)
kc = jnp.asarray(rng.normal(size=(B, Hkv, nC, cs, d)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(B, Hkv, nC, cs, d)), jnp.float32)
cent = jnp.asarray(kc.mean(3), jnp.float32)
for pos in (5, 37, 128):
    ref = retrieval_decode_attention(q, kc, vc, cent, jnp.asarray(pos), cs=cs, top_b=4)
    with jax.sharding.set_mesh(mesh):
        sh = lambda *a: NamedSharding(mesh, P(*a))
        out = jax.jit(lambda q, k, v, c, p: retrieval_decode_attention_sharded(
            q, k, v, c, p, cs=cs, top_b=4, seq_axes=("data", "model")))(
            q,
            jax.device_put(kc, sh(None, None, ("data", "model"), None, None)),
            jax.device_put(vc, sh(None, None, ("data", "model"), None, None)),
            jax.device_put(cent, sh(None, None, ("data", "model"), None)),
            jnp.asarray(pos),
        )
    err = float(jnp.max(jnp.abs(np.asarray(ref) - np.asarray(out))))
    assert err < 1e-5, ("retrieval", pos, err)

# --- 4) fused owner-local cache write + attend (iteration 4)
from repro.models.retrieval_attention import (
    clustered_cache_update, retrieval_update_and_attend_sharded)
kn = jnp.asarray(rng.normal(size=(B, Hkv, d)), jnp.float32)
vn = jnp.asarray(rng.normal(size=(B, Hkv, d)), jnp.float32)
for pos in (0, 36, 99):
    kc2, vc2, cent2 = clustered_cache_update(kc, vc, cent, kn, vn, jnp.asarray(pos), cs)
    ref = retrieval_decode_attention(q, kc2, vc2, cent2, jnp.asarray(pos + 1), cs=cs, top_b=4)
    with jax.sharding.set_mesh(mesh):
        sh = lambda *a: NamedSharding(mesh, P(*a))
        out, ks, vs, cs_o = jax.jit(lambda *a: retrieval_update_and_attend_sharded(
            *a, cs=cs, top_b=4, seq_axes=("data", "model")))(
            q,
            jax.device_put(kc, sh(None, None, ("data", "model"), None, None)),
            jax.device_put(vc, sh(None, None, ("data", "model"), None, None)),
            jax.device_put(cent, sh(None, None, ("data", "model"), None)),
            kn, vn, jnp.asarray(pos))
    assert float(jnp.max(jnp.abs(np.asarray(ref) - np.asarray(out)))) < 1e-5, ("fused out", pos)
    assert float(jnp.max(jnp.abs(np.asarray(kc2) - np.asarray(ks)))) < 1e-6, ("fused cache", pos)
    assert float(jnp.max(jnp.abs(np.asarray(cent2) - np.asarray(cs_o)))) < 1e-6, ("fused cent", pos)
print("SHARDED_EQUIVALENCE_OK")
"""


def test_sharded_paths_match_reference():
    script = _SCRIPT.replace("%SRC%", str(ROOT / "src"))
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert "SHARDED_EQUIVALENCE_OK" in res.stdout, res.stdout[-2000:] + res.stderr[-2000:]
