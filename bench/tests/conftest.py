"""CPU rehearsal of the benchmark: tiny cells over the real program.

``tiny_root`` is a checkout of its own: the program (``src``, linked),
the benchmark's metric readers (copied), and a ``BENCHMARK.json`` with
two tiny cells whose configuration and traffic files are written the way
the real ones are.  ``run_cell`` drives ``harness.main`` there without
looking for a chip and returns the result line.
"""
import contextlib
import io
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = {
    "tiny-cos": dict(n_items=2000, dim=32, cluster_cap=100, metric="cosine", storage_dtype="float16", k=20),
    "tiny-l2": dict(n_items=2000, dim=32, cluster_cap=100, metric="l2", storage_dtype="float32", k=5),
}
MIXES = {
    "tiny-sessions": {"rate_per_s": 20, "k": 20, "b": 8, "more_k": 20, "think_mean_s": 0.2,
                      "warmup_sessions": 2},
    "tiny-search": {"rate_per_s": 20, "k": 5, "b": 8, "more_k": 0, "think_mean_s": 0.0,
                    "warmup_sessions": 2},
}
CELLS = {"tiny.sessions": ("tiny-cos", "tiny-sessions"), "tiny.search": ("tiny-l2", "tiny-search")}


def write_root(root: Path) -> Path:
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copytree(REPO / "bench" / "metrics", bench / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    base = json.loads((REPO / "bench" / "configs" / "ecpfs-siglip-1152.json").read_text())
    configs = []
    for name, over in TINY.items():
        cfg = dict(base, name=name, **over)
        cfg["serving"] = dict(base["serving"], cache_max_bytes=over["n_items"] * over["dim"] * 4 // 10)
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        configs.append(dict(real["configs"][0], name=name, file=f"bench/configs/{name}.json"))
    for name, mix in MIXES.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    raw = dict(real, configs=configs, workloads=[
        dict(real["workloads"][0], name=cell, config=c, traffic=t) for cell, (c, t) in CELLS.items()
    ])
    for m in raw["end_to_end"] + raw["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(CELLS) if len(m["workloads"]) > 1 else ["tiny.sessions"]
    (root / "BENCHMARK.json").write_text(json.dumps(raw, indent=1))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return write_root(tmp_path)


@pytest.fixture(autouse=True)
def _restore_jax_config():
    """The harness turns JAX's persistent cache on for its checkout; put
    the process's settings back so other tests compile as before."""
    import jax

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def run_cell(root: Path, *argv) -> dict:
    from bench.lib.harness import main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert main(list(argv), root=root, require_tpu=False) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
