"""The trace reduction on synthetic traces and on one recorded on the CPU."""
import pytest

from bench.lib import trace as tr


def ev(name, start, end, **stats):
    return tr.Event(name, float(start), float(end), stats)


def synthetic():
    ops = [
        ev("%_grouped_call.1 = (f32[1,1,128]) custom-call(s8[1,256,1152] %codes)", 100, 300),
        ev("%copy-start = f32[1,1,2] copy-start(%prm)", 250, 350),   # overlaps the kernel
        ev("%_grouped_call.1 = (f32[1,1,128]) custom-call(s8[1,512,1152] %codes)", 600, 700),
        ev("%fusion.3 = f32[8] fusion(%x)", 950, 1100),               # runs past the window
    ]
    spans = [
        ev("window", 0, 1000),
        ev("search", 0, 500),
        ev("search", 400, 900),
        ev("wait_arrival", 350, 600),
    ]
    return tr.Trace({"/device:TPU:0": ops}, spans)


def test_union_merges_overlaps_and_sorts():
    assert tr.union([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == [(1, 4.5), (5, 6)]


def test_busy_is_the_union_of_intervals_inside_the_window():
    red = tr.reduce(synthetic(), {"grouped_topk": ["_grouped_call"]})
    # [100, 350] + [600, 700] + [950, 1000] = 250 + 100 + 50 ns
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(400e-9)
    assert red.idle_share == pytest.approx(0.6)


def test_kernel_time_is_summed_by_name():
    red = tr.reduce(synthetic(), {"grouped_topk": ["_grouped_call"], "none": ["nothing"]})
    assert red.kernel_s["grouped_topk"] == pytest.approx(300e-9)
    assert "none" not in red.kernel_s


def test_breakdown_names_ops_and_gaps():
    red = tr.reduce(synthetic(), {})
    assert red.device_ops[0] == ["_grouped_call.1", pytest.approx(300e-9)]
    assert [n for n, _ in red.device_ops] == ["_grouped_call.1", "fusion.3", "copy-start"]
    # gaps, longest first: [350,600] while a search ran and the generator
    # waited, [700,950] and [0,100] inside searches
    got = [(name, round(s * 1e9)) for name, s in red.idle_gaps]
    assert got == [("search+wait_arrival", 250), ("search", 250), ("search", 100)]


def test_a_trace_with_no_device_reads_no_busy_time():
    red = tr.reduce(tr.Trace({}, [ev("window", 0, 10)]), {"k": ["x"]})
    assert red.busy_s == 0 and red.kernel_s == {}


def test_load_reads_the_benchmark_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("search"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = tr.load(tmp_path)
    assert sorted(s.name for s in got.spans) == ["search", "window"]
    assert got.devices == {}  # the CPU backend has no /device:TPU plane
    red = tr.reduce(got, {})
    assert red.window_s > 0
