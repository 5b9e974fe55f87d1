"""Run eCP-FS's device path once on one TPU chip, at the paper's width.

The deployment is the paper's own (``configs/ecpfs_paper.py``): 1152-d
vectors stored as float16, cosine, cluster cap 455, L=2, b=64, k=100.

  1. ``--n-items`` vectors (400k by default, see ``DEFAULT_N``) come
     from ``data.clustered_vectors(--seed)``;
     ``build_index`` builds the file structure and ``convert`` writes it
     as a v3 blob with int8 companion codes.
  2. Two ``Server``s answer the same 16 queries from that blob, each as
     ``search(q, k=100, b=64)`` and one ``more(sid, 100)``: the quantized
     scan, which launches the grouped Pallas kernel once per traversal
     round, and the plain blob scan.  Their ids and distances must be
     identical; recall against exact brute force is printed.
  3. The same parity check runs on an l2 index of ``--l2-items`` vectors
     at the same width.  There the kernel's distances decide which
     candidates the error bounds prune, so it checks kernel precision.
  4. Packed mode (the whole hierarchy in HBM as float32) is sized against
     the device's memory at the paper's N, then serves one batch of the
     queries at the largest N whose packed index fits.

Everything runs in this one process, which holds the chip.  It needs a
TPU and exits non-zero without one.  Its last line of output is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

    python chip_smoke.py [--n-items N] [--l2-items N] [--seed S]

The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` in the checkout; a second run finds its kernels there.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
WORK = ROOT / ".chip_smoke"  # index files; removed when the run ends

N_QUERIES = 16
# The paper's 1M does not fit one chip machine's 45 GiB of disk writes: the
# blob gives every node a block sized for the largest leaf, and this
# data's largest leaf grows with N (6,900 rows at 400k against a mean of
# 455), so the 1M blob would hold about 65 GB.  400k writes about 25 GB.
DEFAULT_N = 400_000
PACKED_HBM_SHARE = 0.6  # packed index + one scan's gather, of free HBM


class Phases:
    """Wall time per phase, printed as each one ends."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    def run(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.seconds[name] = time.perf_counter() - t0
        print(f"phase {name}: {self.seconds[name]} s", flush=True)
        return out


class CompileClock:
    """Seconds XLA spent compiling (persistent-cache reads included), and
    how many programs came from the persistent cache."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.programs += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def exact_topk(data: np.ndarray, q: np.ndarray, k: int, metric: str) -> np.ndarray:
    """Brute-force ids of the k nearest rows of ``data`` per query."""
    from repro.core.distances import np_distances

    rows = 65_536  # bounds the float32 temporaries of one block
    d = np.concatenate(
        [np_distances(q, data[lo : lo + rows], metric) for lo in range(0, len(data), rows)],
        axis=1,
    )
    top = np.argpartition(d, k, axis=1)[:, :k]
    order = np.argsort(np.take_along_axis(d, top, axis=1), axis=1, kind="stable")
    return np.take_along_axis(top, order, axis=1)


def recall(got: np.ndarray, truth: np.ndarray) -> float:
    k = truth.shape[1]
    return float(np.mean([len(set(g[g >= 0]) & set(t)) / k for g, t in zip(got, truth)]))


def make_queries(data: np.ndarray, seed: int) -> np.ndarray:
    """Held-out queries: perturbed copies of random collection rows."""
    rng = np.random.default_rng(seed + 1)
    rows = rng.choice(len(data), N_QUERIES, replace=False)
    noise = 0.01 * rng.normal(size=(N_QUERIES, data.shape[1]))
    return (data[rows] + noise).astype(np.float32)


def build_blob(data, cfg, name: str, phases: Phases) -> Path:
    from repro.core import build_index, convert

    fs = WORK / f"{name}_fs"
    phases.run(f"{name}/build", build_index, data, str(fs), cfg)
    blob = phases.run(f"{name}/convert", convert, str(fs), WORK / f"{name}.blob", quant="int8")
    shutil.rmtree(fs)  # the blob holds everything the servers read
    print(f"{name}: blob {blob.stat().st_size} B", flush=True)
    return blob


def serve(index, queries: np.ndarray, k: int, b: int) -> dict:
    """search + one more() per query through a Server; collects pages."""
    from repro.launch.serve import Server

    ids, dists, launches = [], [], 0
    with Server(index) as srv:
        for q in queries:
            rs, sid = srv.search(q, k=k, b=b)
            more = srv.more(sid, k)
            ids.append(np.stack([rs.ids, more.ids]))
            dists.append(np.stack([rs.dists, more.dists]))
            launches += more.stats.kernel_launches
            srv.close(sid)
    return {"ids": np.stack(ids), "dists": np.stack(dists), "launches": launches}


def check_kernel_path() -> int:
    """The quantized scan ran the compiled Pallas kernel: ``impl="auto"``
    resolves to it, its jit cache holds the shapes it ran at, and its
    lowering is a Mosaic custom call.  Returns the shapes compiled."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.distance_topk.grouped import _grouped_call
    from repro.kernels.distance_topk.ops import resolve_impl

    impl = resolve_impl("auto")
    check(impl == "pallas", f"grouped op resolves to {impl!r}, not the compiled kernel")
    shapes = _grouped_call._cache_size()
    check(shapes > 0, "the grouped Pallas kernel never ran")
    spec = (
        jax.ShapeDtypeStruct((1, 1, 1152), jnp.float32),
        jax.ShapeDtypeStruct((1, 512, 1152), jnp.int8),
        jax.ShapeDtypeStruct((1, 1, 2), jnp.float32),
        jax.ShapeDtypeStruct((1, 1, 1), jnp.int32),
    )
    text = _grouped_call.lower(
        *spec, k=128, metric="cosine", qformat="int8", bn=128, interpret=False
    ).as_text()
    check("tpu_custom_call" in text, "grouped kernel does not lower to a Mosaic call")
    return shapes


def parity_phase(name, data, cfg, b, k, phases: Phases, seed: int) -> None:
    """Quantized-scan server vs plain blob server on one blob: identical
    ids and distances for every search page and continuation."""
    from repro.core import open_index

    blob = build_blob(data, cfg, name, phases)
    queries = make_queries(data, seed)
    quant = phases.run(
        f"{name}/serve_quantized", serve,
        open_index(blob, mode="file", backend="blob", quantized=True), queries, k, b,
    )
    plain = phases.run(
        f"{name}/serve_plain", serve,
        open_index(blob, mode="file", backend="blob"), queries, k, b,
    )
    check(np.array_equal(quant["ids"], plain["ids"]), f"{name}: quantized ids differ from plain")
    check(
        np.array_equal(quant["dists"], plain["dists"]),
        f"{name}: quantized distances differ from plain",
    )
    check(quant["launches"] > 0, f"{name}: the quantized scan launched no kernel")
    truth = phases.run(f"{name}/brute_force", exact_topk, data, queries, 2 * k, cfg.metric)
    first = recall(quant["ids"][:, 0], truth[:, :k])
    both = recall(quant["ids"].reshape(len(queries), -1), truth)
    print(
        f"{name}: parity ok over {len(queries)} queries x (search + more); "
        f"kernel_launches={quant['launches']}; recall@{k}={first}; "
        f"recall@{2 * k} (search + more)={both}",
        flush=True,
    )


def packed_specs(path, dim: int) -> dict:
    """Shapes of ``BatchedSearcher.arrays`` for the index at ``path``:
    each level padded to its largest node, as ``load_packed`` does."""
    import jax
    import jax.numpy as jnp

    from repro.core import layout, open_store

    store = open_store(path)
    try:
        info = layout.IndexInfo.from_attrs(store.read_attrs(layout.INFO))
        levels = []
        for lv, n_nodes in enumerate(info.nodes_per_level, start=1):
            rows = store.node_rows([(lv, j) for j in range(n_nodes)])
            levels.append((n_nodes, -(-max(max(rows), 1) // 8) * 8))
    finally:
        store.close()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    return {
        "root": spec((info.nodes_per_level[0], dim), jnp.float32),
        "int_emb": [spec((n, c, dim), jnp.float32) for n, c in levels[:-1]],
        "int_ids": [spec((n, c), jnp.int32) for n, c in levels[:-1]],
        "int_mask": [spec((n, c), jnp.bool_) for n, c in levels[:-1]],
        "leaf_emb": spec((*levels[-1], dim), jnp.float32),
        "leaf_ids": spec(levels[-1], jnp.int32),
        "leaf_mask": spec(levels[-1], jnp.bool_),
    }


def nbytes(specs) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(specs))


def scan_memory(specs: dict, batch: int, k: int, b: int, metric: str):
    """``memory_analysis`` of packed mode's largest program, the leaf scan,
    compiled for this device at these shapes (nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    from repro.core.batched import BatchedQueryState, rank_leaves, scan_chunk

    dim = specs["root"].shape[1]
    q = jax.ShapeDtypeStruct((batch, dim), jnp.float32)
    rank, rank_d = jax.eval_shape(
        lambda a, x: rank_leaves(a, x, metric=metric, b_internal=b), specs, q
    )
    C = max(4 * k, 256)  # BatchedSearcher.search's default buffer
    state = BatchedQueryState(
        leaf_rank=rank, leaf_rank_d=rank_d,
        next_ptr=jax.ShapeDtypeStruct((batch,), jnp.int32),
        buf_d=jax.ShapeDtypeStruct((batch, C), jnp.float32),
        buf_i=jax.ShapeDtypeStruct((batch, C), jnp.int32),
    )
    return scan_chunk.lower(specs, q, state, metric=metric, b=b).compile().memory_analysis()


def packed_phase(data, cfg, k: int, b: int, phases: Phases, seed: int) -> None:
    """Size packed mode against the device's memory at the paper's N, then
    serve one batch from it at the largest N that fits."""
    import jax

    from repro.core import build_index, open_index
    from repro.launch.serve import Server

    dev = jax.devices()[0]
    stats = dev.memory_stats()
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    budget = PACKED_HBM_SHARE * free
    path = WORK / "paper.blob"
    n, dim = data.shape
    specs = packed_specs(path, dim)
    arrays = nbytes(specs)
    max_leaf = specs["leaf_emb"].shape[1]
    per_query = b * max_leaf * dim * 4  # one query's gather of b padded leaves
    print(
        f"packed @ N={n}: arrays {arrays} B (largest leaf {max_leaf} rows), "
        f"scan gather {per_query} B per query; HBM free {free} B of "
        f"bytes_limit {stats['bytes_limit']} B; arrays fit: {arrays <= free}",
        flush=True,
    )
    # the batch takes at most half the budget, the index the rest
    batch = N_QUERIES
    while batch > 1 and batch * per_query > budget / 2:
        batch //= 2
    if arrays + batch * per_query > budget:
        # the arrays grow about linearly in N: scale N to the budget
        n = int(n * (budget - batch * per_query) / arrays) // 10_000 * 10_000
        check(n > 0, "packed mode cannot hold even 10k vectors")
        path = WORK / "packed_fs"
        phases.run("packed/build", build_index, data[:n], str(path), cfg)
        specs = packed_specs(path, dim)
    mem = phases.run("packed/compile_scan", scan_memory, specs, batch, k, b, cfg.metric)
    need = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    print(
        f"packed serves N={n}, batch {batch}: scan program needs {need} B "
        f"(arguments {mem.argument_size_in_bytes}, temp {mem.temp_size_in_bytes}, "
        f"output {mem.output_size_in_bytes}) of {free} B free",
        flush=True,
    )
    check(need <= free, f"packed scan at N={n} needs {need} B > {free} B free")
    queries = make_queries(data[:n], seed)[:batch]
    with Server(open_index(str(path), mode="packed")) as srv:
        rs, sid = phases.run("packed/search", srv.search, queries, k=k, b=b)
        more = phases.run("packed/more", srv.more, sid, k)
    check(rs.ids.shape == (batch, k), f"packed ids shape {rs.ids.shape}")
    check(bool(np.all(np.isfinite(rs.dists))), "packed search returned non-finite distances")
    check(bool(np.all(more.ids >= 0)), "packed continuation returned short pages")
    truth = phases.run("packed/brute_force", exact_topk, data[:n], queries, k, cfg.metric)
    print(
        f"packed: peak_bytes_in_use={dev.memory_stats().get('peak_bytes_in_use')} B; "
        f"recall@{k}={recall(rs.ids, truth)}",
        flush=True,
    )


def run(args, clock: CompileClock) -> None:
    from repro.configs.ecpfs_paper import build_cfg, ecpfs_paper_full
    from repro.core import ECPBuildConfig
    from repro.data import clustered_vectors

    paper = ecpfs_paper_full()
    n = args.n_items
    if n != paper.n_items:
        print(
            f"N cut: {n} of the paper's {paper.n_items} vectors (the blob's "
            "leaf-sized blocks must fit the machine's disk)",
            flush=True,
        )
    print(f"N={n} dim={paper.dim} metric={paper.metric} cap={paper.cluster_cap} "
          f"L={paper.levels} b={paper.b} k={paper.k}", flush=True)
    phases = Phases()
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir()
    try:
        data, _ = phases.run(
            "paper/generate", clustered_vectors, args.seed, n=n, dim=paper.dim
        )
        parity_phase("paper", data, build_cfg(paper), paper.b, paper.k, phases, args.seed)

        l2_data, _ = phases.run(
            "l2/generate", clustered_vectors, args.seed + 7, n=args.l2_items, dim=paper.dim
        )
        l2_cfg = ECPBuildConfig(
            levels=paper.levels, metric="l2", cluster_cap=paper.cluster_cap,
            storage_dtype=paper.storage_dtype,
        )
        parity_phase("l2", l2_data, l2_cfg, paper.b, paper.k, phases, args.seed)
        del l2_data
        print(
            f"grouped kernel: compiled Pallas (tpu_custom_call), "
            f"{check_kernel_path()} distinct shapes compiled",
            flush=True,
        )

        packed_phase(data, build_cfg(paper), paper.k, paper.b, phases, args.seed)
        print(
            f"XLA compile seconds: {clock.seconds} over {clock.programs} programs "
            f"({clock.cache_hits} from the persistent cache)",
            flush=True,
        )
        print("phases (s): " + json.dumps(phases.seconds))
    finally:
        shutil.rmtree(WORK, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-items", type=int, default=DEFAULT_N)
    ap.add_argument("--l2-items", type=int, default=50_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}; run it in a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's devices: {devices})", file=sys.stderr)
        return 1
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(f"device: platform={device['platform']} kind={device['kind']} count={device['count']}")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    run(args, clock)
    print(f"total: {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
