"""The collection and its held-out queries.

A copy of the program's mixture-of-Gaussians generator
(``repro.data.synthetic.clustered_vectors``): unit-norm centers, each
vector a center plus isotropic noise of standard deviation ``spread``.
It runs as one jitted call on the default device, so a run pays
milliseconds for it instead of seconds of host sampling.

The collection and the queries are the configuration's: both are made
anew in every run from its ``data_seed``, so every run serves the same
index the same queries; the run's seed orders them (``traffic.py``).
The queries come from the same mixture (the same centers) and never
from the collection: held out, as in the ANN benchmarks' train/test
split.  Data drawn from the run's seed changed the work with the seed:
the eCP build's leaf sizes follow which leaders become hubs, and three
seeds gave gist-960-l2 blobs of 1.8 to 5.7 GB and recall@10 of 0.59 to
0.74; queries drawn from it moved recall@100 by 6% between runs (PERF.md).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int, stream: int):
    """A JAX key for any whole-number seed (the driver's exceed 32 bits)."""
    return jax.random.key(np.random.SeedSequence([int(seed), stream]).generate_state(1)[0])


@partial(jax.jit, static_argnames=("n", "dim", "n_clusters", "spread", "dtype"))
def _draw(k_centers, k_rows, *, n, dim, n_clusters, spread, dtype):
    centers = jax.random.normal(k_centers, (n_clusters, dim), jnp.float32)
    centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)
    k_which, k_noise = jax.random.split(k_rows)
    which = jax.random.randint(k_which, (n,), 0, n_clusters)
    x = centers[which] + spread * jax.random.normal(k_noise, (n, dim), jnp.float32)
    return x.astype(dtype)


def _rows(cfg: dict, k_rows, n: int, dtype) -> np.ndarray:
    gen = cfg["generator"]
    x = _draw(
        key_of(cfg["data_seed"], 0), k_rows, n=n, dim=cfg["dim"],
        n_clusters=gen["n_clusters"], spread=gen["spread"], dtype=dtype,
    )
    return np.asarray(x)


def collection(cfg: dict) -> np.ndarray:
    """``[n_items, dim]`` in the configuration's storage dtype."""
    return _rows(cfg, key_of(cfg["data_seed"], 1), cfg["n_items"], cfg["storage_dtype"])


def queries(cfg: dict, n: int) -> np.ndarray:
    """``[n, dim]`` float32 held-out queries."""
    return _rows(cfg, key_of(cfg["data_seed"], 2), n, "float32")
