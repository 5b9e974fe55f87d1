"""Find a cell's knee: the highest offered rate the server sustains.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates 0.5,1,1.5

Builds and warms the cell's index once, as a run does, then offers each
rate in turn for ``--seconds``, open loop, with the cell's mix otherwise
unchanged.  A rate is sustained when at least 98% of the requests due in
its window were answered by one second after the window closed, and the
median latency of the last third of its searches is at most twice that
of the first third (no growing queue).  It prints one JSON line per rate
and stops after the first rate that is not sustained.  Needs a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.lib import harness, spec as spec_mod  # noqa: E402
from bench.lib.client import OpenLoop  # noqa: E402
from bench.lib.traffic import schedule  # noqa: E402


def rate_stats(loop: OpenLoop, seconds: float) -> dict:
    sent = loop.requests
    answered = [r for r in sent if r.done is not None and r.done <= loop.t_end + 1.0]
    searches = sorted((r for r in loop.of("search") if r.done is not None), key=lambda r: r.due)
    lat = np.array([r.latency_s for r in searches]) * 1e3
    third = max(1, len(lat) // 3)
    first, last = np.median(lat[:third]), np.median(lat[-third:])
    return {
        "offered": len(sent),
        "answered_share": len(answered) / max(1, len(sent)),
        "search_p50_ms": float(np.median(lat)),
        "search_p95_ms": float(np.percentile(lat, 95)),
        "first_third_p50_ms": float(first),
        "last_third_p50_ms": float(last),
        "answered_per_s": len(answered) / seconds,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True, help="comma-separated arrivals per second")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"sweep: no TPU here ({jax.devices()})", file=sys.stderr)
        return 1
    spec = spec_mod.load(ROOT)
    cell = spec.workload(args.workload)
    run = harness.Run(cell=cell, cfg=spec.config(cell["config"]), mix=spec.traffic(cell["traffic"]),
                      seconds=args.seconds, t_start=T_START, work=spec.bench_dir / ".work" / cell["config"],
                      index_dir=spec.bench_dir / ".index" / cell["config"], root=ROOT)
    harness._cache_dir(spec.bench_dir)
    from bench.lib.compile_clock import CompileClock
    from bench.lib.data import collection, queries

    clock = CompileClock()
    shutil.rmtree(run.work, ignore_errors=True)
    run.work.mkdir(parents=True)
    try:
        data = collection(run.cfg)
        rates = [float(r) for r in args.rates.split(",")]
        most = schedule(dict(run.mix, rate_per_s=max(rates)), args.seed, args.seconds)
        pool = queries(run.cfg, most.n_queries)
        warm = schedule(run.mix, args.seed, args.seconds)
        srv, _, _ = harness.open_server(run, data, pool, warm, clock, lambda n: nullcontext())
        try:
            for i, rate in enumerate(rates):
                mix = dict(run.mix, rate_per_s=rate)
                loop = OpenLoop(srv, pool, mix)
                loop.run(schedule(mix, args.seed + 1 + i, args.seconds), time.perf_counter(), args.seconds)
                st = rate_stats(loop, args.seconds)
                st["sustained"] = st["answered_share"] >= 0.98 and st["last_third_p50_ms"] <= 2 * st["first_third_p50_ms"]
                print(json.dumps({"workload": cell["name"], "rate_per_s": rate, **st}), flush=True)
                if not st["sustained"]:
                    break
        finally:
            srv.shutdown()
    finally:
        shutil.rmtree(run.work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
