"""chip_smoke.py's contract off the chip, and its packed-mode sizing."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

import chip_smoke
from repro.core import ECPBuildConfig, build_index, open_index
from repro.data import clustered_vectors

ROOT = Path(__file__).resolve().parents[1]


def test_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_packed_specs_match_the_packed_searcher(tmp_path):
    data, _ = clustered_vectors(3, n=900, dim=16, n_clusters=6)
    path = str(tmp_path / "idx")
    build_index(data, path, ECPBuildConfig(levels=2, cluster_cap=40, metric="cosine"))
    specs = chip_smoke.packed_specs(path, 16)
    arrays = open_index(path, mode="packed").arrays
    got = jax.tree.map(lambda a: (a.shape, a.dtype), arrays)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), specs)
    assert got == want
    assert chip_smoke.nbytes(specs) == sum(a.nbytes for a in jax.tree.leaves(arrays))
    mem = chip_smoke.scan_memory(specs, batch=2, k=10, b=4, metric="cosine")
    assert mem.argument_size_in_bytes >= chip_smoke.nbytes(specs)


def test_exact_topk_and_recall():
    rng = np.random.default_rng(0)
    data = rng.normal(size=(70_000, 8)).astype(np.float32)  # spans two blocks
    q = data[[5, 69_999]] + 1e-4
    top = chip_smoke.exact_topk(data, q, 3, "l2")
    assert top[:, 0].tolist() == [5, 69_999]
    d = ((data[None] - q[:, None]) ** 2).sum(-1)
    assert np.array_equal(top, np.argsort(d, axis=1, kind="stable")[:, :3])
    assert chip_smoke.recall(top, top) == 1.0
    assert chip_smoke.recall(np.full_like(top, -1), top) == 0.0
