"""The trace reduction, on a small trace recorded on a TPU v5e chip.

``data/fit_small.xplane.pb``: two warm ``fit`` calls of a 4 x 4 Ising
lattice (n = 512) inside a ``bench_window`` annotation, each call in a
``bench_call`` annotation and followed by a 20 ms sleep.
"""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "fit_small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce_trace(str(DATA))


def test_window_and_busy_time(red):
    assert 0.1 < red.window_s < 0.2
    assert 0.0 < red.busy_s < 0.01 * red.window_s
    assert 99.0 < red.idle_pct < 100.0


def test_module_seconds_add_up_to_busy_time(red):
    # nested ops (a while loop and its body) are counted once
    assert sum(red.module_s.values()) == pytest.approx(red.busy_s,
                                                       rel=1e-6)
    assert red.module_seconds("jit__solve_bucket") > 0.5 * red.busy_s
    assert red.module_seconds("jit_cl_score_channels") > 0
    assert red.module_seconds("no_such_module") == 0.0


def test_breakdown_is_bounded_and_sorted(red):
    b = red.breakdown()
    for key in ("device_ops", "idle_gaps"):
        vals = [v for _, v in b[key]]
        assert 0 < len(vals) <= 10
        assert vals == sorted(vals, reverse=True)
    assert sum(v for _, v in red.idle) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert b["device_ops"][0][0].startswith("jit__solve_bucket:")


def test_idle_gaps_take_the_innermost_host_label(red):
    # unlabelled gaps of the recorded trace fall inside a span that covers
    # the window; gaps inside the trace's shorter annotations keep those
    assert "host:unlabelled" in dict(red.idle)
    whole = trace.reduce_trace(str(DATA), host_spans=[(-1.0, 10.0, "x")])
    labels = dict(whole.idle)
    assert "host:unlabelled" not in labels
    assert labels["x"] == pytest.approx(dict(red.idle)["host:unlabelled"])
    assert labels["annotation:bench_call"] == pytest.approx(
        dict(red.idle)["annotation:bench_call"])


def test_interval_helpers():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.union_length([]) == 0
    assert trace.gaps([(1, 2), (1.5, 3), (4, 5)], 0, 6) == [
        (0, 1), (3, 4), (5, 6)]
    assert trace.module_name("jit__solve_bucket(1649912)") \
        == "jit__solve_bucket"
    assert trace.op_name("%fusion.12 = f32[4]{0} fusion(...)") \
        == "fusion.12"


def test_trace_without_window_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.reduce_trace(str(tmp_path))
