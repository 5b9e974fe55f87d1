"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Set-up, in this order, is what ``setup_s`` times (from the process's
start): make the configuration's collection and, from the run's seed,
the queries; build the index with the program's ``build_index`` and
``convert(quant=...)`` it to a blob (the scratch file structure is
deleted), or find the blob an earlier run in this checkout built from
the same configuration and program (``index_key``); open it quantized in
file mode behind a ``Server``; warm up every grouped-kernel shape the
index's leaves can give a search, then run the mix's warm-up sessions.

The window drives the open-loop client for ``--seconds``.  With
``--trace 1`` the profiler records it, the benchmark's spans mark what
the host was doing, and the per-layer metrics are read; with
``--trace 0`` the end-to-end metrics are.  Every run compares every page
the window returned with the reference (``reference.py``) once the
window has closed and the server is shut down.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import reference, spec as spec_mod, trace as trace_mod
from .client import OpenLoop
from .traffic import schedule

# the grouped kernel's events carry its jitted wrapper's name in their HLO text
KERNELS = {"grouped_topk": ("_grouped_call",)}


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json on this machine's chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--control", choices=("bf16", "bf16-rank"),
        help="answer the window's requests with the reference instead of the program, ranked in "
        "bfloat16 and reporting bfloat16 (bf16) or exact (bf16-rank) distances",
    )
    return ap.parse_args(argv)


@dataclass
class Run:
    """What a metric's ``read(run)`` can look at."""

    cell: dict
    cfg: dict
    mix: dict
    seconds: float
    setup_s: float = 0.0
    loop: OpenLoop | None = None
    compiles_in_window: int = 0
    bytes_read: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    recall: float | None = None
    reduction: trace_mod.Reduction | None = None
    kernel_work: dict = field(default_factory=dict)  # rows, groups, launches
    peaks: dict | None = None
    memory_peak: int = 0
    t_start: float = 0.0
    work: Path | None = None       # scratch of this run, deleted at its end
    index_dir: Path | None = None  # the checkout's built index of this configuration
    root: Path | None = None       # the checkout

    def answered(self, kind: str) -> list:
        return [r for r in self.loop.of(kind) if r.done is not None]


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def _cache_dir(bench_dir: Path) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(bench_dir / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # most grouped-kernel shapes compile in under a second: keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _kop(rows: int, depth: int) -> int:
    """The top-k width the quantized scan asks of the kernel for a leaf of
    ``rows`` rows at rerank depth ``depth`` (``core/search.py``,
    ``_quant_scan_round``)."""
    return min(rows, -(-(depth + 16) // 32) * 32)


def warm_shapes(index, cfg: dict, mix: dict) -> int:
    """Run the grouped kernel once at every shape a leaf of this index can
    give a search: one (query, leaf) group per launch, at the search
    page's rerank depth.  A more page launches nothing here: the search's
    increment staged every scanned row's candidate, far more than the
    next page takes (``compiles_in_window`` would show one that did).
    Returns the shapes run."""
    from repro.kernels.distance_topk import ops

    level = index.info.levels
    sizes = index.store.node_rows([(level, j) for j in range(index.info.nodes_per_level[-1])])
    dim = cfg["dim"]
    shapes = {(int(n), _kop(int(n), int(mix["k"]))) for n in sizes if n > 0}
    for n, kop in sorted(shapes):
        ops.grouped_distance_topk(
            np.zeros((1, dim), np.float32), np.zeros((1, n, dim), np.int8),
            np.ones(1, np.float32), np.zeros(1, np.float32), np.array([n], np.int32),
            kop, cfg["metric"], cfg["quant"],
        )
    return len(shapes)


class KernelWork:
    """Counts the live rows handed to the grouped kernel (traced runs)."""

    def __init__(self):
        from repro.kernels.distance_topk import ops

        self._ops = ops
        self._orig = ops.grouped_distance_topk
        self._lock = threading.Lock()
        self.work = {"launches": 0, "groups": 0, "rows": 0}

    def __enter__(self):
        def counted(q, codes, scales, offsets, n_rows, *a, **kw):
            with self._lock:
                self.work["launches"] += 1
                self.work["groups"] += len(n_rows)
                self.work["rows"] += int(np.sum(n_rows))
            return self._orig(q, codes, scales, offsets, n_rows, *a, **kw)

        self._ops.grouped_distance_topk = counted
        return self

    def __exit__(self, *exc):
        self._ops.grouped_distance_topk = self._orig


def index_key(cfg: dict, root: Path) -> str:
    """What the built index depends on: the configuration, the benchmark's
    generator and every source file of the program."""
    h = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    h.update((Path(__file__).parent / "data.py").read_bytes())
    src = Path(root) / "src"
    for p in sorted((src / "repro").rglob("*.py")):
        h.update(p.relative_to(src).as_posix().encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:20]


def build(cfg: dict, data: np.ndarray, work: Path, store: Path, key: str) -> Path:
    """The configuration's blob: ``store/<key>/index.blob`` when an earlier
    run in this checkout built it, else built now with the program's
    ``build_index`` and ``convert`` and kept there in place of any other
    key's (one index per configuration)."""
    from repro.core import ECPBuildConfig, build_index, convert

    blob = store / key / "index.blob"
    if blob.is_file():
        log(f"index {key} from the checkout's cache, blob {blob.stat().st_size} B")
        return blob
    shutil.rmtree(store, ignore_errors=True)
    part = store / f"{key}.part"
    part.mkdir(parents=True)
    t = time.perf_counter()
    build_cfg = ECPBuildConfig(
        levels=cfg["levels"], metric=cfg["metric"], cluster_cap=cfg["cluster_cap"],
        storage_dtype=cfg["storage_dtype"],
        seed=cfg["data_seed"],
    )
    build_index(data, str(work / "fs"), build_cfg)
    log(f"build {time.perf_counter() - t} s")
    t = time.perf_counter()
    convert(str(work / "fs"), part / "index.blob", quant=cfg["quant"])
    shutil.rmtree(work / "fs")
    part.rename(store / key)  # whole or absent: a run cut during the build leaves a .part
    log(f"convert {time.perf_counter() - t} s, blob {blob.stat().st_size} B")
    return blob


def open_server(run: Run, data, pool, sched, clock, span):
    """Build, open and warm the index behind a ``Server``.  Returns the
    server, the index and the list the scheduler's pinned snapshots are
    collected in."""
    from repro.core import open_index
    from repro.launch.serve import Server

    cfg, mix = run.cfg, run.mix
    blob = build(cfg, data, run.work, run.index_dir, index_key(cfg, run.root))
    index = open_index(
        blob, mode="file", backend="blob", quantized=True,
        cache_max_bytes=cfg["serving"]["cache_max_bytes"],
    )
    views = []  # the pinned snapshots the scheduler serves from
    pin = index.snapshot

    def snapshot():
        snap = pin()
        views.append(snap)
        inner = snap.search

        def search(*a, **kw):
            with span("search"):
                return inner(*a, **kw)

        snap.search = search
        return snap

    index.snapshot = snapshot
    srv = Server(index, workers=cfg["serving"]["workers"], queue_depth=cfg["serving"]["queue_depth"])
    t = time.perf_counter()
    n_shapes = warm_shapes(index, cfg, mix)
    log(f"warm-up: {n_shapes} kernel shapes in {time.perf_counter() - t} s; "
        f"{clock.programs} programs so far, {clock.cache_hits} from the persistent cache, "
        f"{clock.seconds} s compiling")
    t = time.perf_counter()
    for qi in sched.warmup:
        _, sid = srv.search(pool[qi], int(mix["k"]), b=int(mix["b"]))
        if mix.get("more_k"):
            srv.more(sid, int(mix["more_k"]))
        srv.close(sid)
    log(f"warm-up: {len(sched.warmup)} sessions in {time.perf_counter() - t} s")
    return srv, index, views


def serve_window(run: Run, args, data, pool, sched, clock, trace_dir: Path | None) -> None:
    """Set-up from the generated collection to the window's close."""
    import jax

    traced = trace_dir is not None
    span = jax.profiler.TraceAnnotation if traced else (lambda name: nullcontext())
    srv, index, views = open_server(run, data, pool, sched, clock, span)
    try:
        run.loop = OpenLoop(srv, pool, run.mix, span=span)
        io0 = sum(v.store.io.bytes_read for v in views) + index.store.io.bytes_read
        hits0, misses0 = index.cache.hits, index.cache.misses
        progs0 = clock.programs
        counter = KernelWork() if traced else nullcontext()
        run.setup_s = time.perf_counter() - run.t_start
        if traced:
            jax.profiler.start_trace(str(trace_dir))
        with counter:
            t0 = time.perf_counter()
            with span("window"):
                run.loop.run(sched, t0, run.seconds)
        if traced:
            jax.profiler.stop_trace()
            run.kernel_work = counter.work
        run.compiles_in_window = clock.programs - progs0
        run.bytes_read = sum(v.store.io.bytes_read for v in views) + index.store.io.bytes_read - io0
        run.cache_hits = index.cache.hits - hits0
        run.cache_misses = index.cache.misses - misses0
        run.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()
        )
    finally:
        srv.shutdown()


def control_window(run: Run, data, pool, sched, control: str) -> None:
    """A control in the program's place: the window's sessions answered
    by the reference ranked in bfloat16, each with its search and its more."""
    from .client import Request, Session

    mix = run.mix
    run.setup_s = time.perf_counter() - run.t_start
    run.loop = OpenLoop(None, pool, mix)
    for due, q, think in zip(sched.due_s, sched.query, sched.think_s):
        s = Session(query=int(q), think_s=float(think))
        for kind in ("search", "more") if mix.get("more_k") else ("search",):
            r = Request(kind, float(due), int(q), s)
            s.requests.append(r)
            run.loop.requests.append(r)
        run.loop.sessions.append(s)
    reference.control_pages(
        data, pool, run.loop.sessions, run.cfg["metric"], int(mix["k"]), int(mix.get("more_k", 0)),
        exact=control == "bf16-rank",
    )
    run.loop.t_end = float("inf")


def compare(run: Run, data, pool) -> dict:
    mix = run.mix
    sizes = {"search": int(mix["k"]), "more": int(mix.get("more_k", 0))}
    checks = reference.check_pages(
        run.loop.sessions, pool, data, run.cfg["metric"], lambda r: sizes[r.kind]
    )
    searches = run.answered("search")
    if searches:
        qs = sorted({r.query for r in searches})
        truth = dict(zip(qs, reference.knn(data, pool[qs], sizes["search"], run.cfg["metric"])))
        run.recall = reference.recall(
            np.stack([np.asarray(r.ids) for r in searches]), np.stack([truth[r.query] for r in searches])
        )
    return checks


def main(argv=None, *, root: Path = spec_mod.ROOT, require_tpu: bool = True, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    src = Path(root) / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under {src}: run from a checkout of the repository", file=sys.stderr)
        return 2
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    spec = spec_mod.load(root)
    cell = spec.workload(args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"bench: no TPU here (JAX's devices: {devices})", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache {_cache_dir(spec.bench_dir)}")
    from .compile_clock import CompileClock
    from .data import collection, queries
    from .peaks import peaks

    clock = CompileClock()
    run = Run(cell=cell, cfg=cfg, mix=mix, seconds=args.seconds, t_start=t_start,
              work=spec.bench_dir / ".work" / cell["config"],
              index_dir=spec.bench_dir / ".index" / cell["config"], root=Path(root))
    shutil.rmtree(run.work, ignore_errors=True)
    run.work.mkdir(parents=True)
    trace_dir = run.work / "trace" if args.trace else None
    try:
        t = time.perf_counter()
        sched = schedule(mix, args.seed, args.seconds)
        data = collection(cfg)
        pool = queries(cfg, sched.n_queries)
        log(f"generate {time.perf_counter() - t} s")
        if args.control:
            control_window(run, data, pool, sched, args.control)
        else:
            serve_window(run, args, data, pool, sched, clock, trace_dir)
        log(f"setup_s {run.setup_s}")
        t = time.perf_counter()
        checks = compare(run, data, pool)
        log(f"reference {time.perf_counter() - t} s")
        if trace_dir is not None:
            t = time.perf_counter()
            run.reduction = trace_mod.reduce(trace_mod.load(trace_dir), KERNELS)
            log(f"trace reduction {time.perf_counter() - t} s")
        if devices[0].platform == "tpu":
            run.peaks = peaks(devices[0].device_kind)
    finally:
        shutil.rmtree(run.work, ignore_errors=True)
    result = _result(spec, run, args, devices, checks)
    for kind in ("search", "more"):
        lat = sorted(round(r.latency_s * 1e3, 3) for r in run.answered(kind))
        if lat and not args.control:
            log(f"{kind} latencies (ms): {lat}")
    for name, c in result["checks"].items():
        print(f"bench: check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def _result(spec, run: Run, args, devices, checks: dict) -> dict:
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in [] if args.control else spec.metrics_for(run.cell["name"], kind):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    sent = run.loop.requests
    failed = sum(1 for r in sent if r.done is None)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(run.memory_peak),
    }
    out = {
        "correct": reference.verdict(checks) and len(sent) > 0,
        "attempted": len(sent),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if run.reduction is not None:
        device["busy_s"] = run.reduction.busy_s
        device["window_s"] = run.reduction.window_s
        out["breakdown"] = {
            "device_ops": run.reduction.device_ops,
            "idle_gaps": run.reduction.idle_gaps,
        }
    out["checks"] = {n: {"value": v, "limit": reference.LIMITS[n]} for n, v in checks.items()}
    return out
