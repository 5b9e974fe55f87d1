"""Concurrent serving subsystem: BlobStore pin/COW, ECPSnapshot parity
under writes, reader/writer stress, scheduler backpressure + deadlines,
session cap/TTL, bounded ServeStats, prefetch-accuracy counters."""
import threading
import time

import numpy as np
import pytest

from repro.core import (
    BlobSnapshot,
    BlobStore,
    ECPBuildConfig,
    ECPSnapshot,
    QueryClosedError,
    build_index,
    convert,
    open_index,
)
from repro.core import layout
from repro.launch.scheduler import (
    DeadlinePolicy,
    RequestScheduler,
    ServerOverloadedError,
    SnapshotManager,
)
from repro.launch.serve import LatencyRing, Server, ServeStats


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    from repro.data import clustered_vectors

    data, _ = clustered_vectors(11, n=6000, dim=24, n_clusters=48)
    path = tmp_path_factory.mktemp("serve_idx") / "ecp"
    build_index(
        data, str(path), ECPBuildConfig(levels=2, metric="l2", cluster_cap=80, seed=4)
    )
    blob = convert(str(path), tmp_path_factory.mktemp("serve_blob") / "idx.blob")
    return data, str(path), str(blob)


def _fresh_blob(built, tmp_path):
    import shutil

    _, _, blob = built
    dst = tmp_path / "idx.blob"
    shutil.copy(blob, dst)
    return str(dst)


# ------------------------------------------------------------ BlobStore MVCC
def test_blob_pin_snapshot_reads_survive_overwrite(built, tmp_path):
    blob = _fresh_blob(built, tmp_path)
    bs = BlobStore(blob)
    emb0, ids0 = bs.get_node(1, 0)
    snap = bs.pin()
    assert isinstance(snap, BlobSnapshot) and snap.backend == "blob+snapshot"
    # overwrite the node in the LIVE store (COW because a pin exists);
    # doubling is exact in the blob's f16 storage dtype
    bs.write_node(1, 0, emb0 * 2.0, ids0 + 1000)
    e_live, i_live = bs.get_node(1, 0)
    e_snap, i_snap = snap.get_node(1, 0)
    np.testing.assert_array_equal(e_snap, emb0)
    np.testing.assert_array_equal(i_snap, ids0)
    np.testing.assert_array_equal(e_live, emb0 * 2.0)
    np.testing.assert_array_equal(i_live, ids0 + 1000)
    snap.close()
    bs.close()


def test_blob_snapshot_is_read_only_and_idempotent_close(built, tmp_path):
    bs = BlobStore(_fresh_blob(built, tmp_path))
    snap = bs.pin()
    with pytest.raises(PermissionError):
        snap.write_node(1, 0, np.zeros((1, 24), np.float32), np.zeros(1, np.int64))
    with pytest.raises(PermissionError):
        snap.write_attrs(layout.INFO, {})
    with pytest.raises(PermissionError):
        snap.free_slot(1, 0)
    assert not snap.closed
    snap.close()
    snap.close()  # idempotent
    assert snap.closed
    bs.close()


def test_blob_retired_slots_recycle_after_release(built, tmp_path):
    bs = BlobStore(_fresh_blob(built, tmp_path))
    emb, ids = bs.get_node(1, 0)
    snap = bs.pin()
    bs.write_node(1, 0, emb + 1, ids)  # COW -> old slot retired, not freed
    assert bs._retired, "overwrite under a pin must retire the old slot"
    snap.close()
    assert not bs._retired, "releasing the last pin recycles retired slots"
    bs.close()


def test_blob_free_slot_retires_while_pinned(built, tmp_path):
    bs = BlobStore(_fresh_blob(built, tmp_path))
    snap = bs.pin()
    emb, ids = snap.get_node(1, 1)
    bs.free_slot(1, 1)
    # the snapshot still reads the freed node's bytes
    e2, i2 = snap.get_node(1, 1)
    np.testing.assert_array_equal(e2, emb)
    np.testing.assert_array_equal(i2, ids)
    snap.close()
    bs.close()


def test_blob_snapshot_survives_compact_replace(built, tmp_path):
    """os.replace of the blob file must not invalidate a pinned snapshot
    (it holds its own dup'd fd)."""
    blob = _fresh_blob(built, tmp_path)
    idx = open_index(blob, mode="file", backend="blob")
    emb, ids = idx.store.get_node(1, 0)
    snap_store = idx.store.pin()
    idx.insert(np.random.default_rng(0).normal(size=(32, 24)).astype(np.float32))
    idx.compact()  # rewrites the file via os.replace
    e2, i2 = snap_store.get_node(1, 0)
    np.testing.assert_array_equal(e2, emb)
    np.testing.assert_array_equal(i2, ids)
    snap_store.close()
    idx.close()


# ------------------------------------------------------------- ECPSnapshot
def test_ecp_snapshot_bit_identical_under_mutation(built, tmp_path):
    data, _, _ = built
    blob = _fresh_blob(built, tmp_path)
    idx = open_index(blob, mode="file", backend="blob")
    rng = np.random.default_rng(2)
    qs = data[rng.integers(0, len(data), 12)]
    snap = idx.snapshot()
    assert isinstance(snap, ECPSnapshot)
    before = [snap.search(q, k=20, b=8) for q in qs]
    # mutate the live index heavily past the pinned generation
    base = int(idx.info.next_id)
    idx.insert(
        data[:200] + 0.01 * rng.normal(size=(200, 24)).astype(np.float32),
        np.arange(base, base + 200),
    )
    idx.delete(np.arange(0, 300, 5))
    idx.compact()
    after = [snap.search(q, k=20, b=8) for q in qs]
    for rs0, rs1 in zip(before, after):
        np.testing.assert_array_equal(rs0.ids, rs1.ids)
        np.testing.assert_array_equal(rs0.dists, rs1.dists)
    # live index sees the mutations; snapshot-vs-live may differ
    live = idx.search(qs[0], k=20, b=8)
    assert 0 not in live.row_ids(0) or 0 not in set(np.arange(0, 300, 5))
    snap.close()
    idx.close()


def test_ecp_snapshot_continuation_survives_compact(built, tmp_path):
    data, _, _ = built
    idx = open_index(_fresh_blob(built, tmp_path), mode="file", backend="blob")
    snap = idx.snapshot()
    rs = snap.search(data[0], k=10, b=4)
    idx.compact()  # live queries would now raise StaleQueryError
    more = rs.query.next(10)  # snapshot continuation keeps its generation
    assert more.ids.shape[-1] == 10
    rs.query.close()
    snap.close()
    idx.close()


def test_ecp_snapshot_refuses_writes(built, tmp_path):
    idx = open_index(_fresh_blob(built, tmp_path), mode="file", backend="blob")
    snap = idx.snapshot()
    with pytest.raises(PermissionError):
        snap.insert(np.zeros((1, 24), np.float32))
    with pytest.raises(PermissionError):
        snap.delete([0])
    with pytest.raises(PermissionError):
        snap.compact()
    snap.close()
    idx.close()


def test_ecp_snapshot_unsupported_on_fstore(built):
    _, path, _ = built
    idx = open_index(path, mode="file", backend="fstore")
    with pytest.raises(NotImplementedError):
        idx.snapshot()
    idx.close()


# ------------------------------------------- concurrent reader/writer stress
def test_concurrent_readers_one_writer_stress(built, tmp_path):
    """Reader threads search pinned snapshots while a writer inserts,
    deletes, and compacts: no torn reads (every search returns k valid
    rows), no StaleQueryError, and a snapshot re-query is bit-identical."""
    data, _, _ = built
    idx = open_index(_fresh_blob(built, tmp_path), mode="file", backend="blob")
    mgr = SnapshotManager(idx)
    rng = np.random.default_rng(5)
    qs = data[rng.integers(0, len(data), 8)]
    errors: list = []
    stop = threading.Event()

    def reader(tid):
        r = np.random.default_rng(tid)
        try:
            while not stop.is_set():
                lease = mgr.lease()
                try:
                    q = qs[r.integers(0, len(qs))]
                    rs1 = lease.search(q, k=10, b=6)
                    rs2 = lease.search(q, k=10, b=6)  # same pin -> identical
                    np.testing.assert_array_equal(rs1.ids, rs2.ids)
                    np.testing.assert_array_equal(rs1.dists, rs2.dists)
                    assert rs1.ids.shape[-1] == 10
                finally:
                    lease.release()
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    def writer():
        r = np.random.default_rng(77)
        try:
            for i in range(6):
                base = int(idx.info.next_id)
                idx.insert(
                    r.normal(size=(48, 24)).astype(np.float32),
                    np.arange(base, base + 48),
                )
                mgr.refresh()
                if i == 2:
                    idx.delete(np.arange(0, 120, 7))
                    mgr.refresh()
                if i == 4:
                    idx.compact()
                    mgr.refresh()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    readers = [threading.Thread(target=reader, args=(t,)) for t in range(4)]
    wt = threading.Thread(target=writer)
    for t in readers:
        t.start()
    wt.start()
    wt.join()
    stop.set()
    for t in readers:
        t.join()
    mgr.close()
    idx.close()
    assert not errors, errors


# ---------------------------------------------------------------- scheduler
class _StubRS:
    def __init__(self, k):
        self.ids = np.zeros(k, np.int64)
        self.dists = np.zeros(k, np.float32)
        self.query = type("Q", (), {"close": lambda s: None, "next": lambda s, k: None})()


class _SlowSearcher:
    def __init__(self, delay_s=0.05):
        self.delay_s = delay_s
        self.bs: list = []

    def search(self, q, k, b=None, **opts):
        self.bs.append(b)
        time.sleep(self.delay_s)
        return _StubRS(k)


def test_scheduler_backpressure_rejects_when_full():
    sched = RequestScheduler(_SlowSearcher(0.05), workers=1, queue_depth=1)
    futs, rejected = [], 0
    for _ in range(12):
        try:
            futs.append(sched.submit(np.zeros(4), 5))
        except ServerOverloadedError:
            rejected += 1
    assert rejected > 0
    for f in futs:
        f.result()
    st = sched.stats.as_dict()
    assert st["submitted"] == st["completed"] + st["rejected"] + st["failed"]
    assert st["rejected"] == rejected
    sched.shutdown()


def test_scheduler_deadline_shrinks_b():
    s = _SlowSearcher(0.01)
    sched = RequestScheduler(s, workers=1, queue_depth=8)
    for _ in range(4):  # warm the EWMA with generous deadlines
        sched.search(np.zeros(4), 5, b=64, deadline_ms=10_000)
    r = sched.search(np.zeros(4), 5, b=64, deadline_ms=0.01)
    assert r.b_effective == sched.policy.b_min
    assert s.bs[-1] == sched.policy.b_min  # the searcher really saw it
    assert r.b_requested == 64
    assert sched.stats.as_dict()["degraded"] >= 1
    sched.shutdown()


def test_deadline_policy_ewma_and_clamp():
    p = DeadlinePolicy(b_min=2, alpha=0.5, safety=1.0, init_s_per_b=1e-3)
    assert p.choose_b(100, remaining_s=-1) == 2  # already past deadline
    assert p.choose_b(100, remaining_s=10.0) == 100  # plenty of time
    assert p.choose_b(100, remaining_s=0.01) == 10  # 0.01s / 1e-3 = 10
    p.observe(10, 0.1)  # 0.01 s/b observed -> ewma moves toward it
    assert p.s_per_b == pytest.approx(0.5 * 1e-3 + 0.5 * 0.01)
    p.observe(0, 1.0)  # ignored
    p.observe(10, -1.0)  # ignored
    assert p.s_per_b == pytest.approx(0.5 * 1e-3 + 0.5 * 0.01)


def test_scheduler_worker_error_propagates():
    class Boom:
        def search(self, q, k, b=None, **o):
            raise RuntimeError("kaboom")

    sched = RequestScheduler(Boom(), workers=1, queue_depth=4)
    with pytest.raises(RuntimeError, match="kaboom"):
        sched.submit(np.zeros(4), 5).result()
    st = sched.stats.as_dict()
    assert st["failed"] == 1
    assert st["submitted"] == st["completed"] + st["rejected"] + st["failed"]
    sched.shutdown()


def test_scheduler_holds_blas_at_one_thread_while_up():
    """Workers run numpy's BLAS on one thread while any scheduler is up;
    the last scheduler's shutdown restores the process's own limits."""
    threadpoolctl = pytest.importorskip("threadpoolctl")

    def blas_threads():
        return sorted(
            i["num_threads"] for i in threadpoolctl.threadpool_info() if i["user_api"] == "blas"
        )

    np.ones((2, 2)) @ np.ones((2, 2))  # load numpy's BLAS
    own = blas_threads()
    assert own
    seen = []

    class Probe:
        def search(self, q, k, b=None, **o):
            seen.append(blas_threads())
            return None

    a = RequestScheduler(Probe(), workers=2, queue_depth=4)
    b = RequestScheduler(Probe(), workers=1, queue_depth=4)
    a.search(np.zeros(4), 5)
    a.shutdown()
    a.shutdown()  # idempotent: gives back its hold once
    b.search(np.zeros(4), 5)  # b is still up: still one thread
    assert blas_threads() == [1] * len(own)
    b.shutdown()
    assert seen == [[1] * len(own)] * 2
    assert blas_threads() == own


def test_scheduler_mutate_serializes_with_rwlock_reads():
    """Non-pinning searcher: mutate() must be exclusive with in-flight
    reads (the fstore fallback path)."""
    events = []
    lock = threading.Lock()

    class Tracked:
        def search(self, q, k, b=None, **o):
            with lock:
                events.append("r+")
            time.sleep(0.02)
            with lock:
                events.append("r-")
            return _StubRS(k)

    sched = RequestScheduler(Tracked(), workers=2, queue_depth=8)
    assert sched.snapshots is None
    futs = [sched.submit(np.zeros(4), 5) for _ in range(2)]
    time.sleep(0.005)  # let reads start

    def mut():
        with lock:
            events.append("w+")
        time.sleep(0.01)
        with lock:
            events.append("w-")

    sched.mutate(mut)
    for f in futs:
        f.result()
    sched.shutdown()
    i_w = events.index("w+")
    assert "r+" not in events[i_w : events.index("w-")], events


# ---------------------------------------------------------------- Server
def test_server_sync_mode_unchanged(built):
    _, path, _ = built
    idx = open_index(path, mode="file", backend="fstore")
    with Server(idx) as srv:
        rs, sid = srv.search(np.zeros(24, np.float32), k=5, b=4)
        assert rs.ids.shape[-1] == 5
        srv.more(sid, k=5)
        srv.close(sid)
        with pytest.raises(QueryClosedError):
            srv.more(sid, k=5)
        s = srv.stats.summary()
        assert s["queries"] == 1 and s["continuations"] == 1
        assert s["p50_ms"] is not None


def test_server_concurrent_blob_uses_snapshots(built, tmp_path):
    data, _, _ = built
    idx = open_index(_fresh_blob(built, tmp_path), mode="file", backend="blob")
    with Server(idx, workers=2, queue_depth=8) as srv:
        assert srv.scheduler is not None and srv.scheduler.snapshots is not None
        rs, sid = srv.search(data[0], k=10, b=6)
        base = int(idx.info.next_id)
        srv.insert(
            np.random.default_rng(0).normal(size=(32, 24)).astype(np.float32),
            np.arange(base, base + 32),
        )
        srv.compact()
        # snapshot-backed continuation is immune to the compact
        more = srv.more(sid, k=10)
        assert more.ids.shape[-1] == 10
        srv.close(sid)


def test_server_session_cap_evicts_lru(built):
    _, path, _ = built
    idx = open_index(path, mode="file", backend="fstore")
    with Server(idx, session_cap=3) as srv:
        sids = [srv.search(np.zeros(24, np.float32), k=5, b=4)[1] for _ in range(5)]
        assert srv.open_sessions == 3
        for sid in sids[:2]:  # the two oldest were evicted
            with pytest.raises(QueryClosedError):
                srv.more(sid, k=5)
        srv.more(sids[-1], k=5)  # newest still live
        assert srv.stats.summary()["evicted_sessions"] == 2


def test_server_session_ttl_evicts_idle(built):
    _, path, _ = built
    idx = open_index(path, mode="file", backend="fstore")
    now = [0.0]
    with Server(idx, session_ttl_s=10.0, clock=lambda: now[0]) as srv:
        sid_old = srv.search(np.zeros(24, np.float32), k=5, b=4)[1]
        now[0] = 5.0
        sid_new = srv.search(np.zeros(24, np.float32), k=5, b=4)[1]
        now[0] = 11.0  # old idle 11s > ttl, new idle 6s
        srv.search(np.zeros(24, np.float32), k=5, b=4)  # triggers sweep
        with pytest.raises(QueryClosedError):
            srv.more(sid_old, k=5)
        srv.more(sid_new, k=5)


def test_serve_stats_bounded_and_threadsafe():
    stats = ServeStats(ring_capacity=64)
    threads = [
        threading.Thread(
            target=lambda: [stats.record("search", 1.0) for _ in range(500)]
        )
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ring = stats.ring("search")
    assert ring.count == 2000
    assert len(ring.values()) == 64  # memory stays bounded
    assert stats.summary()["search_p99_ms"] == 1.0


def test_latency_ring_percentiles():
    r = LatencyRing(capacity=8)
    assert r.percentile(50) is None
    for v in [1.0, 2.0, 3.0, 4.0]:
        r.record(v)
    assert r.percentile(50) == pytest.approx(2.5)
    for v in range(100):  # wrap: only the last 8 remain
        r.record(float(v))
    assert r.values().min() == 92.0


# ------------------------------------------------------- prefetch accuracy
def test_prefetch_accuracy_counters(built):
    data, _, blob = built
    idx = open_index(blob, mode="file", backend="blob", prefetch=True, cache_max_nodes=256)
    rng = np.random.default_rng(9)
    for q in data[rng.integers(0, len(data), 8)]:
        idx.search(q, k=20, b=8)
    drain = getattr(idx.store, "drain", None)
    if drain is not None:
        drain()
    idx.flush_prefetch_stats()
    io = idx.store.io
    assert io.prefetch_issued > 0
    assert io.prefetch_hits <= io.prefetch_issued
    d = io.as_dict()
    assert {"prefetch_issued", "prefetch_hits", "prefetch_wasted_bytes"} <= set(d)
    idx.close()


def test_prefetch_counters_absent_without_prefetch(built):
    data, _, blob = built
    idx = open_index(blob, mode="file", backend="blob")
    idx.search(data[0], k=10, b=6)
    assert idx.store.io.prefetch_issued == 0
    assert idx.store.io.prefetch_hits == 0
    idx.close()
