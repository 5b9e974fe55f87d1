"""Closed-loop serving benchmark — concurrent QPS/latency under writes.

The lifecycle section already showed the single-threaded cost of serving
during ingest (search latency inflates ~15x while insert batches run,
because every query waits for the writer).  This benchmark measures what
the concurrent serving subsystem (launch/scheduler.py + launch/serve.py)
buys back: client threads drive a ``Server`` at a target QPS through two
phases —

  readonly   only searches
  mixed      same search load while a writer thread continuously inserts
             batches and occasionally deletes

and each phase reports p50/p99 latency, achieved QPS, and the scheduler's
admission/deadline accounting (rejected / degraded / deadline misses)
with the mean, p50 and p99 of the requests' queue waits.
On the blob backend reads are snapshot-isolated, so the mixed-phase p99
should stay within a small factor of the read-only p99 instead of
absorbing whole insert batches.

CI smoke gate::

  PYTHONPATH=src python -m benchmarks.serving --smoke

runs a tiny version and FAILS on either of the subsystem's two hard
invariants:

  * snapshot parity — a pinned snapshot's results, queried while the
    writer keeps mutating (including across further inserts), must be
    bit-identical to a fresh single-threaded index opened on a copy of
    the blob file taken at the pinned generation;
  * deadline accounting — submitted == completed + rejected + failed and
    deadline_misses <= completed once the load drains.
"""
from __future__ import annotations

import shutil
import threading
import time

import numpy as np


def _percentiles(lat_ms: list) -> tuple[float, float, float]:
    if not lat_ms:
        return 0.0, 0.0, 0.0
    a = np.asarray(lat_ms)
    return float(a.mean()), float(np.percentile(a, 50)), float(np.percentile(a, 99))


class _Clients:
    """Closed-loop client pool: each thread issues its next request when
    the previous one finishes, paced to target_qps/n_clients ticks (if a
    request runs long the next fires immediately — saturation behaves
    closed-loop, light load behaves like a paced open loop)."""

    def __init__(self, server, queries, *, k, b, deadline_ms, target_qps, n_clients):
        self.server = server
        self.queries = queries
        self.k, self.b, self.deadline_ms = k, b, deadline_ms
        self.interval = n_clients / target_qps if target_qps else 0.0
        self.n_clients = n_clients
        self.lat_ms: list = []
        self.queue_wait_ms: list = []  # every answered request's, over all phases
        self.rejected = 0
        self.errors: list = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: list = []

    def _loop(self, tid: int) -> None:
        from repro.launch.scheduler import ServerOverloadedError

        rng = np.random.default_rng(tid)
        next_tick = time.perf_counter()
        while not self._stop.is_set():
            q = self.queries[rng.integers(0, len(self.queries))]
            t0 = time.perf_counter()
            try:
                rs, sid = self.server.search(
                    q, self.k, b=self.b, deadline_ms=self.deadline_ms
                )
                self.server.close(sid)
                dt_ms = (time.perf_counter() - t0) * 1e3
                with self._lock:
                    self.lat_ms.append(dt_ms)
                    self.queue_wait_ms.append(rs.stats.queue_wait_s * 1e3)
            except ServerOverloadedError:
                with self._lock:
                    self.rejected += 1
                time.sleep(self.interval or 1e-3)  # back off, as a client would
            except Exception as e:  # pragma: no cover - surfaced by run()
                with self._lock:
                    self.errors.append(e)
                return
            next_tick += self.interval
            delay = next_tick - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            else:
                next_tick = time.perf_counter()

    def run_for(self, seconds: float) -> dict:
        self.lat_ms, self.rejected = [], 0
        self._stop.clear()
        self._threads = [
            threading.Thread(target=self._loop, args=(i,), daemon=True)
            for i in range(self.n_clients)
        ]
        t0 = time.perf_counter()
        for t in self._threads:
            t.start()
        time.sleep(seconds)
        self._stop.set()
        for t in self._threads:
            t.join()
        if self.errors:
            raise self.errors[0]
        wall = time.perf_counter() - t0
        mean, p50, p99 = _percentiles(self.lat_ms)
        return {
            "completed": len(self.lat_ms),
            "rejected": self.rejected,
            "qps": round(len(self.lat_ms) / wall, 1),
            "mean_ms": round(mean, 3),
            "p50_ms": round(p50, 3),
            "p99_ms": round(p99, 3),
        }


def _writer_loop(server, dim, stop, *, batch=64, period_s=0.005, seed=99):
    """Sustained ingest: insert a batch every ``period_s``, tombstone a
    few ids every 8th batch."""
    rng = np.random.default_rng(seed)
    base = int(server.searcher.info.next_id)
    i = 0
    inserted = deleted = 0
    while not stop.is_set():
        vecs = rng.normal(size=(batch, dim)).astype(np.float32)
        ids = np.arange(base + i * batch, base + (i + 1) * batch)
        server.insert(vecs, ids)
        inserted += batch
        if i % 8 == 7:
            victims = ids[:4]
            deleted += server.delete(victims)
        i += 1
        stop.wait(period_s)
    return inserted, deleted


def run_serving(
    *,
    blob_path: str,
    queries: np.ndarray,
    k: int = 100,
    b: int = 16,
    workers: int = 4,
    n_clients: int = 8,
    target_qps: float = 2000.0,
    deadline_ms: float = 100.0,
    queue_depth: int = 64,
    phase_s: float = 3.0,
    cache_max_nodes: int = 64,
) -> list[dict]:
    """One row per phase (readonly, mixed) for one Server configuration.

    ``blob_path`` may be a single blob file OR a federation root (a
    directory with a ``federation.json`` manifest): ``open_index`` auto-
    detects, and the Server/scheduler machinery is index-agnostic."""
    from repro.core import open_index
    from repro.launch.serve import Server

    idx = open_index(
        blob_path, mode="file", backend="auto", cache_max_nodes=cache_max_nodes
    )
    rows = []
    with Server(idx, workers=workers, queue_depth=queue_depth) as srv:
        clients = _Clients(
            srv,
            queries,
            k=k,
            b=b,
            deadline_ms=deadline_ms,
            target_qps=target_qps,
            n_clients=n_clients,
        )

        r = clients.run_for(phase_s)
        rows.append({"phase": "readonly", **r, "inserts": 0, "deletes": 0})

        stop = threading.Event()
        out: dict = {}

        def writer():
            out["io"] = _writer_loop(srv, queries.shape[1], stop)

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        r = clients.run_for(phase_s)
        stop.set()
        wt.join()
        ins, dels = out["io"]
        rows.append({"phase": "mixed", **r, "inserts": ins, "deletes": dels})

        st = srv.scheduler.stats.as_dict()
        for row in rows:
            row["workers"] = workers
        mean, p50, p99 = _percentiles(clients.queue_wait_ms)
        rows.append(
            {
                "phase": "scheduler",
                "completed": st["completed"],
                "rejected": st["rejected"],
                "qps": "",
                "mean_ms": round(mean, 3),
                "p50_ms": round(p50, 3),
                "p99_ms": round(p99, 3),
                "inserts": st["degraded"],
                "deletes": st["deadline_misses"],
                "workers": workers,
            }
        )
        # accounting invariant (all client futures resolved by now)
        assert st["submitted"] == st["completed"] + st["rejected"] + st["failed"], st
        assert st["deadline_misses"] <= st["completed"], st
    return rows


def run(*, fast: bool = True, phase_s: float | None = None) -> list[dict]:
    """The run.py scenario: serving phases over the shared bench suite's
    blob index."""
    from .indexes import get_suite

    s = get_suite()
    queries = np.stack([t.queries[-1] for t in s.ds.tasks])
    return run_serving(
        blob_path=_suite_blob(s),
        queries=queries,
        k=s.params["k"],
        b=s.params["b"]["eCP-FS"],
        phase_s=phase_s if phase_s is not None else (2.0 if fast else 5.0),
    )


def _suite_blob(s) -> str:
    """The serving run mutates its index; work on a throwaway copy of the
    suite's blob so later sections see the original."""
    dst = s.ecp_blob_path + ".serving"
    shutil.copy(s.ecp_blob_path, dst)
    return dst


def smoke(n: int = 4000, dim: int = 32, phase_s: float = 1.5) -> None:
    """Tiny end-to-end gate: run both phases at load, then assert the two
    hard invariants (snapshot parity under continued mutation + deadline
    accounting).  Raises on violation."""
    import tempfile

    from repro.core import ECPBuildConfig, build_index, convert, open_index
    from repro.data import clustered_vectors
    from repro.launch.serve import Server

    data, _ = clustered_vectors(0, n=n, dim=dim, n_clusters=48)
    with tempfile.TemporaryDirectory() as td:
        path = td + "/idx"
        build_index(data, path, ECPBuildConfig(levels=2, cluster_cap=100, metric="l2"))
        blob = str(convert(path, td + "/idx.blob"))
        rng = np.random.default_rng(3)
        queries = data[rng.integers(0, n, 32)]

        rows = run_serving(
            blob_path=blob,
            queries=queries,
            k=20,
            b=8,
            workers=4,
            n_clients=4,
            target_qps=500.0,
            deadline_ms=50.0,
            phase_s=phase_s,
        )
        for row in rows:
            print(row)
        ro = next(r for r in rows if r["phase"] == "readonly")
        mx = next(r for r in rows if r["phase"] == "mixed")
        assert ro["completed"] > 0 and mx["completed"] > 0, rows
        if ro["p99_ms"]:
            print(
                f"serving smoke: mixed/readonly p99 ratio = "
                f"{mx['p99_ms'] / ro['p99_ms']:.2f}x"
            )

        # ---- snapshot parity under continued mutation --------------------
        idx = open_index(blob, mode="file", backend="blob", cache_max_nodes=64)
        with Server(idx, workers=2, queue_depth=16) as srv:
            base = int(idx.info.next_id)  # the phase run above already inserted
            new = data[:64] + 0.02 * rng.normal(size=(64, dim)).astype(np.float32)
            srv.insert(new, np.arange(base, base + 64))
            srv.delete(np.arange(0, 100, 7))
            # pin a generation and copy the at-rest file atomically w.r.t.
            # writers (snapshot() + the copy both under the mutation lock)
            with idx._mut_lock:
                snap = idx.snapshot()
                frozen = td + "/frozen.blob"
                shutil.copy(blob, frozen)
            # keep mutating PAST the pinned generation
            more = data[64:160] + 0.02 * rng.normal(size=(96, dim)).astype(np.float32)
            srv.insert(more, np.arange(base + 64, base + 160))
            srv.delete(np.arange(1, 100, 9))
            srv.compact()

            ref = open_index(frozen, mode="file", backend="blob")
            for q in queries[:16]:
                rs_snap = snap.search(q, k=20, b=8)
                rs_ref = ref.search(q, k=20, b=8)
                np.testing.assert_array_equal(rs_snap.ids, rs_ref.ids)
                np.testing.assert_array_equal(rs_snap.dists, rs_ref.dists)
            snap.close()
            ref.close()
        print("serving smoke OK: snapshot parity bit-identical; accounting holds")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true", help="tiny phases + hard invariants (CI gate)"
    )
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        for row in run(fast=False):
            print(row)
