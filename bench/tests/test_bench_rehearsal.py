"""A whole run on the CPU at a tiny size, the compiled kernel replaced by
its interpreter: the checks pass, each declared metric is reported, and
a configuration, a mix and a metric added as new files are found by
name with no edit to a file that was there."""
import json

import pytest

from bench.tests.conftest import run_cell

SEED = "2147483659"  # past 32 signed bits, as the driver's are


@pytest.mark.parametrize("cell", ["tiny.sessions", "tiny.search"])
def test_a_run_is_correct_and_reports_its_metrics(tiny_root, cell):
    out = run_cell(tiny_root, "--workload", cell, "--seed", SEED, "--seconds", "1.5", "--trace", "0")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 10
    assert set(out["metrics"]) == {"setup_s", "queries_per_s", "recall_at_k"}
    assert 0 < out["metrics"]["recall_at_k"]["value"] <= 1
    assert list(out)[-1] == "checks" and out["checks"]["dist_gap"]["value"] < 1e-5
    assert out["device"]["platform"] == "cpu"


def test_a_traced_run_reads_the_layers(tiny_root):
    out = run_cell(tiny_root, "--workload", "tiny.sessions", "--seed", "5", "--seconds", "1.5", "--trace", "1")
    assert out["correct"] is True
    m = out["metrics"]
    assert {"search_p50_ms", "search_p95_ms", "leaves_per_search", "launches_per_search", "bytes_read_per_search",
            "node_cache_hit_rate", "generator_lag_p95_ms", "compiles_in_window"} <= set(m)
    assert m["leaves_per_search"]["value"] >= 8 and m["compiles_in_window"]["value"] == 0
    # no chip, so nothing from the device trace: no idle share, no roofline
    assert "device_idle_share" not in m and "grouped_topk_roofline" not in m
    assert out["device"]["window_s"] > 1.5 and set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_grouped_kernel_itself_serves_a_run(tiny_root, monkeypatch):
    from repro.kernels.distance_topk import ops

    monkeypatch.setattr(ops, "resolve_impl", lambda impl="auto": "pallas_interpret")
    out = run_cell(tiny_root, "--workload", "tiny.search", "--seed", "11", "--seconds", "1", "--trace", "0")
    assert out["correct"] is True and out["attempted"] >= 15


def test_new_files_are_found_by_name(tiny_root):
    bench = tiny_root / "bench"
    cfg = json.loads((bench / "configs" / "tiny-l2.json").read_text())
    (bench / "configs" / "tiny-ip.json").write_text(json.dumps(dict(cfg, name="tiny-ip", metric="ip")))
    mix = json.loads((bench / "traffic" / "tiny-search.json").read_text())
    (bench / "traffic" / "tiny-slow.json").write_text(json.dumps(dict(mix, rate_per_s=10)))
    (bench / "metrics" / "searches_answered.py").write_text(
        "def read(run):\n    return float(len(run.answered('search')))\n"
    )
    raw = json.loads((tiny_root / "BENCHMARK.json").read_text())
    raw["configs"].append(dict(raw["configs"][1], name="tiny-ip", file="bench/configs/tiny-ip.json"))
    raw["workloads"].append(dict(raw["workloads"][1], name="tiny.ip", config="tiny-ip", traffic="tiny-slow"))
    raw["end_to_end"].append({"name": "searches_answered", "unit": "queries", "better": "higher",
                              "bound": 0.01, "source": "host_clock", "workloads": ["tiny.ip"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(raw))
    out = run_cell(tiny_root, "--workload", "tiny.ip", "--seed", "3", "--seconds", "1", "--trace", "0")
    assert out["correct"] is True
    assert out["metrics"]["searches_answered"]["value"] == 10


def test_the_built_index_is_kept_once_per_configuration(tiny_root, capfd):
    store = tiny_root / "bench" / ".index" / "tiny-l2"
    args = ("--workload", "tiny.search", "--seconds", "1", "--trace", "0")
    first = run_cell(tiny_root, "--seed", "31", *args)
    (key,) = [p.name for p in store.iterdir()]
    blob = store / key / "index.blob"
    built = blob.stat().st_mtime_ns
    capfd.readouterr()
    second = run_cell(tiny_root, "--seed", "32", *args)
    assert f"index {key} from the checkout's cache" in capfd.readouterr().err
    assert blob.stat().st_mtime_ns == built
    recall = [r["metrics"]["recall_at_k"]["value"] for r in (first, second)]
    assert second["correct"] and recall[1] == pytest.approx(recall[0], rel=1e-12)
    # another configuration under the same name builds anew and replaces it
    path = tiny_root / "bench" / "configs" / "tiny-l2.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), cluster_cap=80)))
    assert run_cell(tiny_root, "--seed", "33", *args)["correct"]
    (other,) = [p.name for p in store.iterdir()]
    assert other != key
