"""TPU-native batched eCP search (level-synchronous beam + resumable state).

The paper's single-query priority queue is inherently sequential; the TPU
adaptation (DESIGN.md §3) restores eCP's per-level synchronization so a
whole query batch advances level-by-level with dense, MXU-friendly distance
blocks and ``lax.top_k`` selections:

  1. score the root centroids, take the best ``b`` lvl_1 nodes;
  2. per internal level: gather children centroid blocks, score, re-top-b;
  3. at the last internal level, *rank* every candidate leaf (not just the
     top-b) — this ranking is the device analogue of the priority queue and
     is what makes the search resumable;
  4. scan ``b`` leaves at a time, merging scanned items into a bounded,
     sorted candidate buffer per query.

``BatchedQueryState`` is a pytree: (leaf ranking, visit pointer, candidate
buffer).  It is owned by a ``BatchedQuery`` handle: ``search`` returns a
``ResultSet`` whose ``.query.next(k)`` emits the best ``k`` unseen items
and advances the state — the batched equivalent of Algorithm 2 behind the
same unified API as the file-mode searcher.  Exhausting the ranked leaf
list mirrors the paper's T-queue running empty.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .api import Query, ResultSet, SearchStats
from .distances import jnp_distances
from .packed import PackedIndex

__all__ = ["BatchedQuery", "BatchedQueryState", "BatchedSearcher"]

# numpy, not jnp: a jnp constant at import would take the accelerator in
# every process that imports repro.core, even one that never searches here
_INF = np.float32(np.inf)


@jax.tree_util.register_pytree_node_class
@dataclass
class BatchedQueryState:
    leaf_rank: jnp.ndarray    # [B, R] int32 leaf ids in visit order (-1 pad)
    leaf_rank_d: jnp.ndarray  # [B, R] centroid distance of each ranked leaf
    next_ptr: jnp.ndarray     # [B] int32 next rank position to visit
    buf_d: jnp.ndarray        # [B, C] sorted candidate distances (+inf pad)
    buf_i: jnp.ndarray        # [B, C] candidate item ids (-1 pad)

    def tree_flatten(self):
        return (self.leaf_rank, self.leaf_rank_d, self.next_ptr, self.buf_d, self.buf_i), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _ascending_top_k(d, ids, k):
    """Smallest-k by distance; returns (d_k, ids_k) ascending."""
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(ids, idx, axis=-1)


# The jitted stages take the packed hierarchy (``BatchedSearcher.arrays``)
# as an ARGUMENT: a jit that closed over it would embed the whole index as
# constants in every compiled program, which the host copies while
# compiling.  As arguments, one compiled program also serves every
# searcher whose arrays have the same shapes.
@partial(jax.jit, static_argnames=("metric", "b_internal"))
def rank_leaves(arrays: dict, q: jnp.ndarray, *, metric: str, b_internal: int):
    """[B, D] queries -> ranked candidate leaves [B, R] (+ distances)."""
    B = q.shape[0]
    d = jnp_distances(q, arrays["root"], metric)           # [B, n1]
    n1 = d.shape[-1]
    int_levels = list(zip(arrays["int_emb"], arrays["int_ids"], arrays["int_mask"]))
    if not int_levels:  # L == 1: root children are the leaves
        order = jnp.argsort(d, axis=-1)
        return order.astype(jnp.int32), jnp.take_along_axis(d, order, axis=-1)
    b = min(b_internal, n1)
    node_d, node = _ascending_top_k(d, jnp.broadcast_to(jnp.arange(n1, dtype=jnp.int32), d.shape), b)
    for li, (emb, ids, mask) in enumerate(int_levels):
        ce = emb[node]                                      # [B, b, maxc, D]
        cd = jnp_distances(q[:, None, None, :], ce, metric)[:, :, 0, :]  # [B, b, maxc]
        cm = mask[node]
        cd = jnp.where(cm, cd, _INF)
        cid = jnp.where(cm, ids[node], -1)
        flat_d = cd.reshape(B, -1)
        flat_i = cid.reshape(B, -1)
        if li == len(int_levels) - 1:
            order = jnp.argsort(flat_d, axis=-1)            # rank ALL leaves seen
            return (
                jnp.take_along_axis(flat_i, order, axis=-1).astype(jnp.int32),
                jnp.take_along_axis(flat_d, order, axis=-1),
            )
        bb = min(b_internal, flat_d.shape[-1])
        node_d, node = _ascending_top_k(flat_d, flat_i, bb)
        node = jnp.maximum(node, 0)                        # guard -1 pads
    raise AssertionError("unreachable")


@partial(jax.jit, static_argnames=("metric", "b"))
def scan_chunk(arrays: dict, q, state: BatchedQueryState, *, metric: str, b: int):
    """Visit the next ``b`` ranked leaves; merge items into the buffer."""
    B = q.shape[0]
    R = state.leaf_rank.shape[1]
    pos = state.next_ptr[:, None] + jnp.arange(b)[None, :]          # [B, b]
    valid = pos < R
    pos_c = jnp.minimum(pos, R - 1)
    leaf = jnp.take_along_axis(state.leaf_rank, pos_c, axis=-1)     # [B, b]
    lvalid = valid & (leaf >= 0)
    leaf_c = jnp.maximum(leaf, 0)
    emb = arrays["leaf_emb"][leaf_c]                                # [B, b, cap, D]
    ids = arrays["leaf_ids"][leaf_c]                                # [B, b, cap]
    mask = arrays["leaf_mask"][leaf_c] & lvalid[..., None]
    cap = emb.shape[2]
    c = emb.reshape(B, b * cap, -1)
    d = jnp_distances(q[:, None, :], c, metric)[:, 0, :]            # [B, b*cap]
    d = jnp.where(mask.reshape(B, -1), d, _INF)
    i = jnp.where(mask.reshape(B, -1), ids.reshape(B, -1), -1)
    # merge with buffer, re-sort, keep best C
    C = state.buf_d.shape[1]
    all_d = jnp.concatenate([state.buf_d, d], axis=-1)
    all_i = jnp.concatenate([state.buf_i, i], axis=-1)
    buf_d, buf_i = _ascending_top_k(all_d, all_i, C)
    return BatchedQueryState(
        leaf_rank=state.leaf_rank,
        leaf_rank_d=state.leaf_rank_d,
        next_ptr=state.next_ptr + b,
        buf_d=buf_d,
        buf_i=buf_i,
    )


class BatchedQuery(Query):
    """Handle over the device-resident state of one batched search call."""

    def __init__(self, searcher: "BatchedSearcher", q: jnp.ndarray, state: BatchedQueryState, *, b: int, single: bool):
        self._searcher = searcher
        self._q = q
        self._state = state
        self._b = b
        self._single = single

    @property
    def state(self) -> BatchedQueryState:
        self._ensure_open()
        return self._state

    def next(self, k: int) -> ResultSet:
        self._ensure_open()
        d, i, self._state = self._searcher._advance(self._q, self._state, k, self._b)
        return self._searcher._result(d, i, self._state, self._single, self)

    def close(self) -> None:
        self._q = None
        self._state = None
        super().close()


class BatchedSearcher:
    """Device-resident packed index + jitted search stages (the ``Searcher``
    for packed mode)."""

    def __init__(self, packed: PackedIndex):
        self.info = packed.info
        self.metric = packed.info.metric
        leaf = packed.leaf
        self.arrays = {
            "root": jnp.asarray(packed.root_emb),
            "int_emb": [jnp.asarray(p.emb) for p in packed.levels[:-1]],
            "int_ids": [jnp.asarray(p.ids) for p in packed.levels[:-1]],
            "int_mask": [jnp.asarray(p.mask) for p in packed.levels[:-1]],
            "leaf_emb": jnp.asarray(leaf.emb),
            "leaf_ids": jnp.asarray(leaf.ids),
            "leaf_mask": jnp.asarray(leaf.mask),
        }

    @partial(jax.jit, static_argnames=("self", "k"))
    def _emit(self, state: BatchedQueryState, k: int):
        out_d = state.buf_d[:, :k]
        out_i = state.buf_i[:, :k]
        C = state.buf_d.shape[1]
        rem_d = jnp.concatenate([state.buf_d[:, k:], jnp.full((state.buf_d.shape[0], k), _INF)], axis=-1)
        rem_i = jnp.concatenate([state.buf_i[:, k:], jnp.full((state.buf_i.shape[0], k), -1, jnp.int32)], axis=-1)
        new = BatchedQueryState(state.leaf_rank, state.leaf_rank_d, state.next_ptr, rem_d[:, :C], rem_i[:, :C])
        return out_d, out_i, new

    # ---------------------------------------------------------------- API
    def search(
        self,
        q,
        k: int = 100,
        *,
        b: int | None = 8,
        b_internal: int | None = None,
        buffer_cap: int | None = None,
    ) -> ResultSet:
        """New batched search over [D] or [B, D] queries -> ``ResultSet``."""
        b = 8 if b is None else int(b)
        q = jnp.asarray(q, jnp.float32)
        single = q.ndim == 1
        if single:
            q = q[None, :]
        B = q.shape[0]
        bi = b_internal if b_internal is not None else max(b, 8)
        leaf_rank, leaf_rank_d = rank_leaves(self.arrays, q, metric=self.metric, b_internal=bi)
        C = buffer_cap if buffer_cap is not None else max(4 * k, 256)
        state = BatchedQueryState(
            leaf_rank=leaf_rank,
            leaf_rank_d=leaf_rank_d,
            next_ptr=jnp.zeros((B,), jnp.int32),
            buf_d=jnp.full((B, C), _INF),
            buf_i=jnp.full((B, C), -1, jnp.int32),
        )
        state = self._scan(q, state, min(b, leaf_rank.shape[1]))
        d, i, state = self._advance(q, state, k, b)
        return self._result(d, i, state, single, BatchedQuery(self, q, state, b=b, single=single))

    def _scan(self, q, state: BatchedQueryState, b: int) -> BatchedQueryState:
        return scan_chunk(self.arrays, q, state, metric=self.metric, b=b)

    def _advance(self, q: jnp.ndarray, state: BatchedQueryState, k: int, b: int):
        """Emit the next k items, scanning further leaves if needed."""
        R = state.leaf_rank.shape[1]
        # scan until every query has k buffered candidates or leaves exhaust
        for _ in range(64):  # hard bound; python loop keeps jit graphs small
            have = jnp.sum(jnp.isfinite(state.buf_d[:, :k]), axis=-1)
            exhausted = state.next_ptr >= R
            if bool(jnp.all((have >= k) | exhausted)):
                break
            state = self._scan(q, state, min(b, R))
        return self._emit(state, k)

    def _result(self, d, i, state: BatchedQueryState, single: bool, query) -> ResultSet:
        d = np.asarray(d, np.float32)
        i = np.asarray(i, np.int64)
        # leaves actually scanned per query (ranked positions visited)
        ptr = np.asarray(state.next_ptr)
        stats = [SearchStats(leaves_opened=int(p)) for p in ptr]
        if single:
            return ResultSet(dists=d[0], ids=i[0], stats=stats[0], query=query)
        return ResultSet(dists=d, ids=i, stats=stats, query=query)

    def __repr__(self) -> str:  # handy in session listings
        return f"BatchedSearcher(levels={self.info.levels}, metric={self.metric!r})"
