"""The per-layer metrics read from the program's own spans and counters
(``SearchStats``): a traced run reports every one, an untraced run none,
and a program whose stats lack the fields gives nothing rather than an
error."""
import json
from types import SimpleNamespace

import pytest

from bench.lib import spec as spec_mod
from bench.tests.conftest import REPO, run_cell

PROGRAM = {
    "queue_wait_p50_ms", "service_p50_ms", "fetch_ms_per_search", "pack_ms_per_search",
    "kernel_wait_ms_per_search", "rerank_ms_per_search", "h2d_bytes_per_search",
    "fp_leaf_scans_per_search",
}


def test_every_program_metric_is_declared():
    raw = json.loads((REPO / "BENCHMARK.json").read_text())
    assert PROGRAM <= {m["name"] for m in raw["per_layer"]}


def test_a_traced_run_reports_the_program_metrics(tiny_root):
    out = run_cell(tiny_root, "--workload", "tiny.sessions", "--seed", "2147483701", "--seconds", "1.5",
                   "--trace", "1")
    assert out["correct"] is True
    m = {name: v["value"] for name, v in out["metrics"].items()}
    assert PROGRAM <= set(m)
    # a worker's service time holds the search's spans; the cell reads them
    assert m["service_p50_ms"] > 0 and m["queue_wait_p50_ms"] >= 0
    assert all(m[n] > 0 for n in ("fetch_ms_per_search", "pack_ms_per_search", "kernel_wait_ms_per_search",
                                  "rerank_ms_per_search", "h2d_bytes_per_search"))
    assert m["fp_leaf_scans_per_search"] >= 0


def test_an_untraced_run_reports_none_of_them(tiny_root):
    out = run_cell(tiny_root, "--workload", "tiny.sessions", "--seed", "17", "--seconds", "1", "--trace", "0")
    assert out["correct"] is True and not PROGRAM & set(out["metrics"])


class _OldStats:
    """Stats of a program without spans: the counts it always had."""

    kernel_launches = 3
    leaves_opened = 8


@pytest.mark.parametrize("name", sorted(PROGRAM))
def test_a_program_without_the_fields_reads_nothing(name):
    page = SimpleNamespace(done=1.0, stats=_OldStats())
    run = SimpleNamespace(answered=lambda kind: [page])
    assert spec_mod.load(REPO).reader(name)(run) is None
