"""The plain reference and the comparison that decides ``correct``.

The reference knows the collection (made from the seed, as stored) and
the metric's formula, and nothing of the program: exact distances in
float64, and brute-force k nearest neighbours over the whole collection.

An eCP-FS answer is approximate by design (Algorithm 3 scans ``b``
leaves), so the comparison holds each page to what any sound answer
must satisfy, over every page the window returned:

  unanswered    requests due in the window that never answered  limit 0
  short         pages with fewer than k ids                      limit 0
  bad_ids       ids out of range, or repeated within a session   limit 0
  unsorted      pages whose distances do not ascend              limit 0
  out_of_order  sessions whose more page, opening no leaf, holds
                a distance below the search page's last one      limit 0
  dist_gap      widest gap between a returned distance and the
                reference's distance of that id, over the median
                reference distance of its page                   ``DIST_GAP_LIMIT``

``dist_gap`` is what the store reads and the host rerank report: the
right rows, scored at the configuration's float32.  ``out_of_order`` is
what they select: a more page that scans no new leaf is the next k of
the candidates the search page was taken from, so a page chosen at lower
precision than it reports (ranked by int8 codes or bfloat16, then given
exact distances) leaves a nearer candidate for the next page.  The two
controls are the reference's own answers in bfloat16: ``bf16`` ranks
and reports in bfloat16 and fails ``dist_gap``; ``bf16-rank`` ranks in
bfloat16 and reports exact distances and fails ``out_of_order``.  A
continuation that does not advance fails ``bad_ids``.  Which leaves the
traversal chose is not compared: that needs the program's tree.
``recall_at_k`` guards it, against the brute-force neighbours.
"""
from __future__ import annotations

import numpy as np

# Set from chip readings (PERF.md, "How correct is decided"): sound runs
# read at most LOWER, the bfloat16 control at least UPPER.
DIST_GAP_LIMIT = 1e-4
BLOCK_ROWS = 16_384  # bounds the float64 temporaries of one block


def distances(q: np.ndarray, rows: np.ndarray, metric: str) -> np.ndarray:
    """Exact float64 distances of ``q`` [D] or [B, D] to ``rows`` [N, D]
    (smaller is nearer): squared euclidean, negative inner product, or
    one minus the cosine."""
    q = np.asarray(q, np.float64)
    rows = np.asarray(rows, np.float64)
    if metric == "l2":
        diff = rows[None] - np.atleast_2d(q)[:, None]
        d = np.einsum("bnd,bnd->bn", diff, diff)
    elif metric == "ip":
        d = -(np.atleast_2d(q) @ rows.T)
    elif metric == "cosine":
        qn = np.atleast_2d(q) / np.linalg.norm(np.atleast_2d(q), axis=1, keepdims=True)
        rn = rows / np.linalg.norm(rows, axis=1, keepdims=True)
        d = 1.0 - qn @ rn.T
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return d[0] if q.ndim == 1 else d


def _block_distances(q: np.ndarray, rows: np.ndarray, metric: str) -> np.ndarray:
    """``distances`` for a query block against a row block, by matrix
    products (the l2 form expands the square; exact enough to rank)."""
    q = np.asarray(q, np.float64)
    rows = np.asarray(rows, np.float64)
    if metric == "l2":
        return (q * q).sum(1)[:, None] + (rows * rows).sum(1)[None] - 2.0 * q @ rows.T
    return distances(q, rows, metric)


def knn(data: np.ndarray, queries: np.ndarray, k: int, metric: str) -> np.ndarray:
    """Brute-force ids of the k nearest rows of ``data`` per query."""
    best_d = np.full((len(queries), 0), np.inf)
    best_i = np.zeros((len(queries), 0), np.int64)
    for lo in range(0, len(data), BLOCK_ROWS):
        block = data[lo : lo + BLOCK_ROWS]
        d = np.concatenate([best_d, _block_distances(queries, block, metric)], axis=1)
        ids = np.arange(lo, lo + len(block))
        i = np.concatenate([best_i, np.broadcast_to(ids, (len(queries), len(ids)))], axis=1)
        top = np.argpartition(d, min(k, d.shape[1]) - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(d, top, axis=1)
        best_i = np.take_along_axis(i, top, axis=1)
    order = np.argsort(best_d, axis=1, kind="stable")
    return np.take_along_axis(best_i, order, axis=1)


def recall(got: np.ndarray, truth: np.ndarray) -> float:
    """Mean share of each row of ``truth`` found in the same row of ``got``."""
    k = truth.shape[1]
    return float(np.mean([len(set(g[g >= 0].tolist()) & set(t.tolist())) / k for g, t in zip(got, truth)]))


def check_pages(sessions, pool, data, metric: str, k_of) -> dict:
    """The compared numbers over every page of ``sessions`` (each a list
    of requests with ``kind``, ``ids``, ``dists``, ``done``).  ``k_of``
    gives a request's page size."""
    n = len(data)
    out = {"unanswered": 0, "short": 0, "bad_ids": 0, "unsorted": 0, "out_of_order": 0, "dist_gap": 0.0}
    for s in sessions:
        seen: set = set()
        q = pool[s.query]
        out["out_of_order"] += int(_out_of_order(s.requests))
        for r in s.requests:
            if r.done is None or r.ids is None:
                out["unanswered"] += 1
                continue
            ids = np.asarray(r.ids, np.int64)
            d = np.asarray(r.dists, np.float64)
            valid = ids >= 0
            if valid.sum() < k_of(r):
                out["short"] += 1
            ids, d = ids[valid], d[valid]
            repeated = len(np.unique(ids)) != len(ids) or bool(np.isin(ids, list(seen)).any())
            out["bad_ids"] += int(repeated or bool((ids >= n).any()))
            seen.update(ids.tolist())
            if len(d) > 1 and np.any(np.diff(d) < 0):
                out["unsorted"] += 1
            ok = ids[ids < n]
            if len(ok):
                ref = distances(q, data[ok], metric)
                scale = max(float(np.median(np.abs(ref))), 1e-12)
                gap = float(np.max(np.abs(d[ids < n] - ref))) / scale
                out["dist_gap"] = max(out["dist_gap"], gap)
    return out


def _out_of_order(requests) -> bool:
    """Whether a page that opened no leaf since the page before it holds
    a distance below that page's last one."""
    prev = None
    for r in requests:
        if r.done is None or r.ids is None:
            return False
        d = np.asarray(r.dists, np.float64)[np.asarray(r.ids) >= 0]
        if prev is not None and len(d) and len(prev[0]) and _leaves(r) == prev[1] and d.min() < prev[0].max():
            return True
        prev = (d, _leaves(r))
    return False


def _leaves(r):
    return getattr(r.stats, "leaves_opened", None)


LIMITS = {"unanswered": 0, "short": 0, "bad_ids": 0, "unsorted": 0, "out_of_order": 0,
          "dist_gap": DIST_GAP_LIMIT}


def verdict(checks: dict) -> bool:
    return all(checks[name] <= LIMITS[name] for name in LIMITS)


def control_pages(data: np.ndarray, pool: np.ndarray, sessions, metric: str, k: int, more_k: int,
                  exact: bool = False):
    """A control: the reference's own answers, ranked in bfloat16.

    Each session's search page is the brute-force top-k and its more page
    the next ``more_k``, ranked from bfloat16 inputs and bfloat16
    distances.  The pages report those bfloat16 distances, or with
    ``exact`` the float64 distances of the ids chosen, each page sorted
    by them.  Fills the requests of ``sessions`` in place."""
    import jax
    import jax.numpy as jnp

    want = k + more_k

    @jax.jit
    def top(qb, rows):
        q = qb.astype(jnp.bfloat16)
        if metric == "l2":
            d = (q.astype(jnp.float32) ** 2).sum(1)[:, None] + (rows.astype(jnp.float32) ** 2).sum(1)[None] - 2.0 * jnp.dot(q, rows.T, preferred_element_type=jnp.float32)
        elif metric == "ip":
            d = -jnp.dot(q, rows.T, preferred_element_type=jnp.float32)
        else:
            qn = q / jnp.linalg.norm(q.astype(jnp.float32), axis=1, keepdims=True).astype(jnp.bfloat16)
            rn = rows / jnp.linalg.norm(rows.astype(jnp.float32), axis=1, keepdims=True).astype(jnp.bfloat16)
            d = 1.0 - jnp.dot(qn, rn.T, preferred_element_type=jnp.float32)
        d = d.astype(jnp.bfloat16)
        neg, idx = jax.lax.top_k(-d.astype(jnp.float32), want)
        return -neg, idx

    rows = jnp.asarray(data).astype(jnp.bfloat16)
    for s in sessions:
        dd, ii = (np.asarray(x)[0] for x in top(jnp.asarray(pool[s.query][None]), rows))
        at = 0
        for r in s.requests:
            size = k if r.kind == "search" else more_k
            ids, d = ii[at : at + size].astype(np.int64), dd[at : at + size]
            if exact:
                d = distances(pool[s.query], data[ids], metric)
                order = np.argsort(d, kind="stable")
                ids, d = ids[order], d[order].astype(np.float32)
            r.ids, r.dists, r.done = ids, d, 0.0
            at += size
