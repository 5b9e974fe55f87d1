"""Spans and per-search counters inside the search path (core/tracing.py).

A quantized search times its store reads, packing, kernel, expansion and
rerank as spans that never overlap, so their sum stays within the
worker's service time; the scheduler sets each request's queue wait; the
kernel counters agree with the launches; each search's ``io`` counts its
own thread's reads; and the pages stay what they were before any of it.
"""
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import ECPBuildConfig, build_index, convert, open_index
from repro.core.api import ResultSet, SearchStats
from repro.core.tracing import span
from repro.data import clustered_vectors
from repro.kernels.distance_topk import grouped
from repro.launch.serve import Server

N, DIM = 3000, 32
SPANS = ("fetch_s", "pack_s", "kernel_s", "expand_s", "rerank_s")
PAGES = Path(__file__).parent / "data" / "tracing_pages.json"


@pytest.fixture(scope="module")
def data():
    return clustered_vectors(0, n=N, dim=DIM, n_clusters=24)[0]


@pytest.fixture(scope="module")
def blob(data, tmp_path_factory):
    d = tmp_path_factory.mktemp("tracing")
    build_index(
        data, str(d / "fs"),
        ECPBuildConfig(levels=2, metric="cosine", cluster_cap=60, storage_dtype="float16"),
    )
    return convert(str(d / "fs"), d / "idx.blob", quant="int8")


def _open(blob, **kw):
    kw.setdefault("quantized", True)
    kw.setdefault("cache_max_bytes", N * DIM * 4 // 10)
    return open_index(blob, mode="file", backend="blob", **kw)


def _queries(data, n=4):
    rng = np.random.default_rng(7)
    return (data[rng.integers(0, N, n)] + 0.1 * rng.standard_normal((n, DIM))).astype(np.float32)


def test_pages_are_bit_identical_to_the_stored_pages(blob, data):
    """The pages of a fixed sequence of searches and continuations, as the
    search path gave them before it had spans."""
    expected = json.loads(PAGES.read_text())["pages"]
    idx = _open(blob)
    Q = _queries(data)
    for q, want in zip(np.concatenate([Q, Q[:2]]), expected):
        rs = idx.search(q, k=20, b=8)
        nx = rs.query.next(20)
        assert rs.ids.tolist() + nx.ids.tolist() == want["ids"]
        assert [float(x) for x in np.concatenate([rs.dists, nx.dists])] == want["dists"]
    idx.close()


def test_span_times_fit_inside_the_service_time(blob, data):
    with Server(_open(blob), workers=2) as srv:
        for q in _queries(data):
            rs, sid = srv.search(q, 20, b=8)
            st = rs.stats
            assert all(getattr(st, f) > 0 for f in SPANS), st
            assert sum(getattr(st, f) for f in SPANS) <= st.service_s
            assert st.queue_wait_s >= 0
            srv.close(sid)


def test_fp_leaf_scans_count_the_leaves_that_bypass_the_kernel(blob, data):
    idx = _open(blob, cache_max_bytes=None)  # every reranked leaf stays cached
    q = _queries(data, 1)[0]
    first = idx.search(q, k=20, b=8).stats
    again = idx.search(q, k=20, b=8).stats
    assert first.fp_leaf_scans < again.fp_leaf_scans
    assert again.kernel_groups + again.fp_leaf_scans == again.leaves_opened
    idx.close()


class _Query:
    def close(self):
        pass


class _HeldSearcher:
    """Holds its first search until released."""

    def __init__(self):
        self.started = threading.Event()
        self.release = threading.Event()
        self.calls = 0

    def search(self, q, k, b=None, **opts):
        self.calls += 1
        if self.calls == 1:
            self.started.set()
            self.release.wait(10)
        return ResultSet(dists=np.zeros(k, np.float32), ids=np.arange(k), stats=SearchStats(), query=_Query())


def test_queue_wait_is_the_time_a_request_waited_for_a_worker():
    hold = 0.3
    held = _HeldSearcher()
    with Server(held, workers=1) as srv:
        first = srv.submit(np.zeros(4), 5)
        assert held.started.wait(10)
        second = srv.submit(np.zeros(4), 5)
        time.sleep(hold)
        held.release.set()
        rs1, _ = first.result(10)
        rs2, _ = second.result(10)
    assert rs1.stats.service_s >= hold and rs1.stats.queue_wait_s < hold
    assert rs2.stats.queue_wait_s >= hold and rs2.stats.service_s < hold


def test_kernel_counters_match_the_launches(blob, data):
    from bench.lib.harness import KernelWork

    from repro.kernels.distance_topk import ops

    idx = _open(blob)
    shapes = []
    orig = ops.grouped_distance_topk

    def seen(q, codes, *a, **kw):
        shapes.append(codes.shape + (codes.dtype.itemsize,))
        return orig(q, codes, *a, **kw)

    ops.grouped_distance_topk = seen
    try:
        with KernelWork() as counted:
            stats = [idx.search(q, k=20, b=8).stats for q in _queries(data)]
    finally:
        ops.grouped_distance_topk = orig
    assert sum(s.kernel_launches for s in stats) == counted.work["launches"] == len(shapes)
    assert sum(s.kernel_rows for s in stats) == counted.work["rows"]
    assert sum(s.kernel_groups for s in stats) == counted.work["groups"]
    assert sum(s.h2d_bytes for s in stats) == sum(grouped.launch_bytes(*s) for s in shapes)
    idx.close()


@pytest.mark.parametrize(
    "G,N,padded",
    [(1, 1, (8, 128)), (3, 128, (8, 128)), (5, 129, (8, 256)), (8, 455, (8, 512)), (9, 455, (16, 512))],
)
def test_launch_bytes_follow_the_wrappers_padding(G, N, padded):
    assert grouped.padded_shape(G, N) == padded
    g, n = padded
    assert grouped.launch_bytes(G, N, 1152, 1) == g * n * 1152 + g * (1152 * 4 + 8 + 4)


@pytest.mark.parametrize("quantized", [True, False])
def test_a_search_counts_only_its_own_reads(blob, data, quantized):
    """Many searches at once on one store (no cache, so each reads the
    same bytes whenever it runs) each count what they read alone."""
    Q = _queries(data, 4)

    def search(idx, q):
        return idx.search(q, k=20, b=8).stats.io.as_dict()

    idx = _open(blob, quantized=quantized, cache_max_bytes=0)
    for q in Q:  # the first search pins the internal levels
        search(idx, q)
    alone = [search(idx, q) for q in Q]
    n_threads = 12  # more than the cores, switching often
    gate = threading.Barrier(n_threads)
    seen = [None] * n_threads

    def run(i):
        gate.wait(30)
        seen[i] = [search(idx, Q[i % len(Q)]) for _ in range(3)]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert all(got == [alone[i % len(Q)]] * 3 for i, got in enumerate(seen))
    assert min(a["bytes_read"] for a in alone) > 0
    idx.close()


def test_a_profiler_trace_holds_the_spans_of_a_scheduled_search(blob, data, tmp_path):
    import jax
    from jax.profiler import ProfileData

    # no cache: every leaf goes through the kernel and the rerank
    with Server(_open(blob, cache_max_bytes=0), workers=1) as srv:
        q = _queries(data, 1)[0]
        jax.profiler.start_trace(str(tmp_path))
        with span("test.main"):
            rs, sid = srv.search(q, 20, b=8)
            srv.more(sid, 20)
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    lines = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in ln.events]
        for plane in ProfileData.from_file(str(pb)).planes
        if plane.name.startswith("/host:")
        for ln in plane.lines
    ]
    main = next(evs for evs in lines if any(n == "test.main" for n, _, _ in evs))
    assert any(n == "serve.more" for n, _, _ in main)  # more runs on the caller's thread
    (worker,) = [evs for evs in lines if any(n == "serve.search" for n, _, _ in evs)]
    assert worker is not main
    (served,) = [(s, e) for n, s, e in worker if n == "serve.search"]
    inner = sorted((s, e, n) for n, s, e in worker if n.startswith("ecp."))
    assert {n for _, _, n in inner} == {"ecp.fetch", "ecp.pack", "ecp.kernel", "ecp.expand", "ecp.rerank"}
    assert all(served[0] <= s and e <= served[1] for s, e, _ in inner)
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))  # they never overlap


class _Stats:
    t_s = 0.0


def test_span_adds_its_seconds_to_the_field():
    st = _Stats()
    with span("x", st, "t_s") as s:
        time.sleep(0.01)
    with span("x", st, "t_s") as s2:
        pass
    assert s.seconds >= 0.01 and st.t_s == pytest.approx(s.seconds + s2.seconds)


def test_span_never_imports_jax():
    """Loaded on its own in a process without jax, the helper times its
    block and leaves jax unimported."""
    path = Path(__file__).resolve().parents[1] / "src" / "repro" / "core" / "tracing.py"
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('tracing', {str(path)!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "class S: t = 0.0\n"
        "s = S()\n"
        "with m.span('ecp.fetch', s, 't'): sum(range(1000))\n"
        "assert s.t > 0 and 'jax' not in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
