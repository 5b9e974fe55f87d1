"""Benchmark entry point — one section per paper table + roofline summary.

  PYTHONPATH=src python -m benchmarks.run [--fast]

Emits, per the harness contract, ``name,us_per_call,derived`` CSV lines in
the SUMMARY section (latencies from the tables; derived = context such as
tasks solved or speedup), after printing each table in full.

With ``--bench-json PATH`` also writes a machine-readable summary: every
scenario's us_per_call plus, where measured, its cold-pass IOStats — so
the perf trajectory is tracked across PRs (the committed
``BENCH_search.json`` comes from the CI bench-smoke invocation,
``--fast --backend all --bench-json BENCH_search.json``).
The search-engine section enforces bit-identical parity
between the legacy and vectorized traversal engines and fails the run on
any mismatch (CI's bench-smoke gate).
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _print_table(title: str, rows: list[dict]) -> None:
    print(f"\n=== {title} ===")
    if not rows:
        print("(empty)")
        return
    cols = list(rows[0].keys())
    widths = {c: max(len(c), *(len(str(r.get(c, ''))) for r in rows)) for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(widths[c]) for c in cols))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller dataset / fewer runs")
    ap.add_argument("--n-items", type=int, default=None)
    ap.add_argument(
        "--backend",
        choices=("fstore", "blob", "blob+prefetch", "all"),
        default="fstore",
        help="eCP-FS node-storage backend for tables 2/4; the backend-"
        "comparison section always reports every backend ('all' repeats "
        "tables 2/4 per backend)",
    )
    ap.add_argument(
        "--bench-json",
        default="",
        help="where to write the machine-readable per-scenario summary "
        "(us_per_call + IOStats).  Off by default so ad-hoc runs don't "
        "clobber the committed artifact; the committed BENCH_search.json "
        "is regenerated with '--fast --backend all --bench-json "
        "BENCH_search.json' (the CI bench-smoke invocation)",
    )
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        backends,
        federation,
        indexes,
        lifecycle,
        recall,
        roofline,
        search_engine,
        serving,
        table2_single_query,
        table3_tasks,
        table4_incremental,
    )

    n_items = args.n_items or (6000 if args.fast else 20000)
    runs = 2 if args.fast else 4
    t0 = time.time()
    suite = indexes.get_suite(n_items=n_items, dim=32, n_tasks=24 if args.fast else 40)
    print(
        f"[bench] suite: {len(suite.ds.data)} items, {len(suite.ds.tasks)} tasks; "
        f"builds: eCP {suite.ecp_build_s:.1f}s IVF {suite.ivf_build_s:.1f}s "
        f"HNSW {suite.hnsw_build_s:.1f}s Vamana {suite.vamana_build_s:.1f}s "
        f"(total {time.time()-t0:.1f}s)"
    )

    ecp_backends = list(indexes.BACKENDS) if args.backend == "all" else [args.backend]

    t2 = []
    for i, be in enumerate(ecp_backends):
        t2.extend(table2_single_query.run(runs=runs, backend=be, baselines=i == 0))
    _print_table("Table 2 — load time + single-query latency (disk/memory) + workload", t2)

    t3 = table3_tasks.run()
    _print_table("Table 3 — tasks completed (target in top-100) + recall@100", t3)

    t4 = []
    for i, be in enumerate(ecp_backends):
        t4.extend(
            table4_incremental.run(rounds=10, runs=max(2, runs // 2), backend=be, baselines=i == 0)
        )
    _print_table("Table 4 — incremental workload: top-100 then 10 x '100 more'", t4)

    tb = backends.run(runs=runs)
    _print_table(
        "Backend comparison — same queries, byte-budgeted cache "
        "(cold-pass IOStats: the file-vs-serialized axis)",
        tb,
    )

    # parity-enforcing: raises on any legacy-vs-vectorized mismatch
    se = search_engine.run(runs=runs)
    _print_table(
        "Search-engine comparison — legacy vs vectorized single-query vs "
        "batch-dedup traversal (bit-identical parity enforced)",
        se,
    )

    # recall/latency frontier over the effort knob b: what the quantized
    # scan buys (or costs) at each recall point vs the plain blob path
    fr = search_engine.run_frontier(runs=runs)
    _print_table(
        "Recall/latency frontier — quantized scan+rerank vs plain blob "
        "batch path per effort b (recall@k vs exact top-k)",
        fr,
    )

    # recall knobs: multi-probe traversal + build-time spill vs the strict
    # best-first baseline (probe_m=1 parity gate enforced inside)
    rk = recall.run(runs=runs)
    _print_table(
        "Recall knobs — probe_m (multi-probe traversal) and spill_s "
        "(build-time replication) vs strict best-first at equal effort b "
        "(recall@10 vs exact)",
        rk,
    )

    lc = lifecycle.run(runs=runs, n_insert=256 if args.fast else 512)
    _print_table(
        "Index lifecycle — build / insert-while-search / delete / compact "
        "throughput (write path)",
        lc,
    )

    # shard federation: the same collection split 4 ways behind one
    # router, compared to the single blob at equal total effort b
    fd = federation.run(fast=args.fast, runs=runs)
    _print_table(
        "Shard federation — scatter-gather over 4 blob shards vs the "
        "single-file index at equal total b (recall@10 vs exact)",
        fd,
    )

    # closed-loop concurrent serving: snapshot-isolated reads vs the
    # single-threaded insert-while-search numbers in the lifecycle section
    sv = serving.run(fast=args.fast)
    _print_table(
        "Concurrent serving — closed-loop QPS/latency, readonly vs "
        "mixed-with-writer (scheduler row: queue waits' mean/p50/p99, "
        "degraded/misses in inserts/deletes cols)",
        sv,
    )

    print("\n=== Roofline (single-pod 16x16, from dry-run artifacts) ===")
    roofline.print_table("single")
    print("\n=== Roofline (multi-pod 2x16x16) ===")
    roofline.print_table("multi")

    # ----------------------------------------------------------- summary CSV
    scenarios: list[dict] = []

    def emit(name: str, us: float, derived: str, io: dict | None = None) -> None:
        print(f"{name},{us:.1f},{derived}")
        row = {"name": name, "us_per_call": round(float(us), 1), "derived": derived}
        if io is not None:
            row["io"] = io
        scenarios.append(row)

    print("\nname,us_per_call,derived")
    for r in t2:
        emit(
            f"table2/{r['index']}/mem",
            r["lat_mem_s"] * 1e6,
            f"disk_us={r['lat_disk_s']*1e6:.1f}",
        )
    for r in t3:
        emit(f"table3/{r['index']}", 0, f"tasks={r['tasks']};recall={r['recall@100']}")
    ecp_wl = next(r for r in t4 if r["index"].startswith("eCP-FS"))["workload_s"]
    for r in t4:
        sp = r["workload_s"] / ecp_wl if ecp_wl else 0.0
        emit(
            f"table4/{r['index']}",
            r["lat_mem_s"] * 1e6,
            f"workload_s={r['workload_s']};vs_ecp={sp:.1f}x",
        )
    for r in tb:
        emit(
            f"backend/{r['backend']}",
            r["lat_cold_s"] * 1e6,
            f"warm_us={r['lat_warm_s']*1e6:.1f};bytes={r['bytes_read']};"
            f"files={r['files_opened']};reads={r['reads_issued']};"
            f"pf={r['prefetch_hits']}/{r['prefetch_issued']}",
            io={
                "bytes_read": r["bytes_read"],
                "files_opened": r["files_opened"],
                "reads_issued": r["reads_issued"],
                "prefetch_issued": r["prefetch_issued"],
                "prefetch_hits": r["prefetch_hits"],
                "prefetch_wasted": r["prefetch_wasted"],
            },
        )
    for r in se:
        # quantized-pipeline rows live under quant/* so the perf
        # trajectory of the compressed scan is trackable on its own
        name = r["scenario"] if r["scenario"].startswith("quant/") else (
            f"search-engine/{r['scenario']}"
        )
        emit(
            name,
            r["us_per_call"],
            f"cold_us={r['cold_us_per_call']};vs_legacy={r['speedup_vs_legacy']}x;"
            f"rounds={r['rounds']};dedup_hits={r['dedup_hits']};"
            f"kernel_launches={r['kernel_launches']}",
            io={
                "bytes_read": r["bytes_read"],
                "files_opened": r["files_opened"],
                "reads_issued": r["reads_issued"],
            },
        )
    for r in fr + rk:
        emit(
            f"frontier/{r['scenario']}",
            r["us_per_call"],
            f"recall={r['recall']};bytes={r['bytes_read']}",
            io={"bytes_read": r["bytes_read"], "reads_issued": r["reads_issued"]},
        )
    for r in lc:
        # us_per_call = per-vector cost of the lifecycle stage
        emit(
            f"lifecycle/{r['scenario']}",
            1e6 / r["vectors_per_s"] if r["vectors_per_s"] else 0.0,
            f"vectors_per_s={r['vectors_per_s']};n={r['n']};{r['extra']}",
        )
    fd_single = next(r for r in fd if r["config"] == "single")
    for r in fd:
        emit(
            f"federation/{r['config']}",
            r["lat_s"] * 1e6,
            f"recall@10={r['recall@10']};b_total={r['b_total']};"
            f"shards={r['shards']};probed={r['probed']};"
            f"recall_gap={fd_single['recall@10'] - r['recall@10']:+.4f}",
            io={"bytes_read": r["bytes"], "reads_issued": r["reads"],
                "leaves_opened": r["leaves"]},
        )
    sv_ro = next(r for r in sv if r["phase"] == "readonly")
    for r in sv:
        if r["phase"] == "scheduler":
            continue
        ratio = r["p99_ms"] / sv_ro["p99_ms"] if sv_ro["p99_ms"] else 0.0
        emit(
            f"serving/{r['phase']}",
            r["p99_ms"] * 1e3,  # us_per_call = p99 latency
            f"p50_ms={r['p50_ms']};qps={r['qps']};completed={r['completed']};"
            f"rejected={r['rejected']};inserts={r['inserts']};"
            f"p99_vs_readonly={ratio:.2f}x",
        )

    if args.bench_json:
        bench = {
            "schema": 1,
            "fast": bool(args.fast),
            "backend": args.backend,
            "n_items": n_items,
            "parity": "ok",  # search_engine.run raised otherwise
            "scenarios": scenarios,
        }
        with open(args.bench_json, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=False)
            f.write("\n")
        print(f"\n[bench] wrote {args.bench_json} ({len(scenarios)} scenarios)")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
