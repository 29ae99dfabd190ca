"""The per-layer readers of the program's own spans and transfer counter,
and the trace reduction labelling idle time with the program's
annotations."""
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import trace
from bench.harness import Context, Layout, Window

LAYOUT = Layout(Path(__file__).resolve().parents[2])


class _Snap:
    """The parts of a TelemetrySnapshot the readers use."""

    def __init__(self, spans, fetches=()):
        self.spans = {k: {"total_s": v} for k, v in spans.items()}
        self.events = [{"kind": "counter", "name": "engine.d2h_bytes",
                        "value": v, "tags": {"site": site}}
                       for site, v in fetches]


def _ctx(snaps):
    return Context(cell={}, config={}, traffic={},
                   window=Window(attempted=len(snaps), failed=0,
                                 end_to_end={}),
                   trace=None, telemetry=snaps, work={}, peak=None)


CALLS = [
    _Snap({"fit": 0.2, "fit/bucket_prep": 0.004, "fit/bucket_solve": 0.02,
           "fit/assemble": 0.010, "fit/combine": 0.1,
           "fit/combine/optimal": 0.09, "fit/score": 0.006},
          [("bucket_solve", 2e6), ("bucket_solve", 1e6), ("score", 1.5e6)]),
    _Snap({"joint": 1.0, "joint/admm_iter/bucket_prep": 0.002,
           "joint/assemble": 0.002, "joint/score": 0.002},
          [("prox_bucket_solve", 0.5e6)]),
]
# per call of the two: the mean over calls of what each reads
EXPECT = {"bucket_prep_ms": 3.0, "assemble_ms": 6.0, "score_ms": 4.0,
          "d2h_mb_per_call": 2.5, "host_fetches_per_call": 2.0}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_averages_per_call_and_is_silent_without_input(name):
    metric = LAYOUT.metric(name)
    assert metric.read(_ctx(CALLS)) == pytest.approx(EXPECT[name])
    # a program without the span or counter (the parent's), or no calls
    assert metric.read(_ctx([_Snap({"fit": 0.2, "fit/combine": 0.1})])) \
        is None
    assert metric.read(_ctx([])) is None


def test_new_metrics_are_declared_for_the_fit_cell():
    declared = {m["name"]: m for m in LAYOUT.metrics_for("fleet_sf.fit",
                                                         "per_layer")}
    for name in EXPECT:
        assert declared[name]["moves"] == "estimate_ms"
        assert declared[name]["workloads"] == ["fleet_sf.fit"]


def test_idle_gap_takes_the_program_annotation(tmp_path, monkeypatch):
    """A CPU trace of a tiny fit has no device operations, so the whole
    window is one gap; a combiner that holds the host for most of the call
    puts the gap's middle inside its annotation."""
    import repro.api as A
    from repro.core.graphs import chain_graph
    from repro.telemetry import TelemetrySpec

    g = chain_graph(4)
    X = np.random.RandomState(0).choice(
        [-1.0, 1.0], size=(100, g.p)).astype(np.float32)
    sess = A.Plan(graph=g, combiners=("uniform",),
                  telemetry=TelemetrySpec()).session()
    sess.fit(X)                                     # compile outside
    slow = sess.combiners[0]
    inner = slow.combine

    def held(*args, **kwargs):
        time.sleep(0.5)
        return inner(*args, **kwargs)

    monkeypatch.setattr(slow, "combine", held)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            sess.fit(X)
    finally:
        jax.profiler.stop_trace()
    red = trace.reduce_trace(str(tmp_path))
    assert [label for label, _ in red.idle] == [
        "annotation:fit/combine/uniform"]
