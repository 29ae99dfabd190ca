"""Per-layer metric readers, on the recorded chip trace and hand-made
telemetry."""
from pathlib import Path

import numpy as np
import pytest

from bench import inputs, trace, work
from bench.harness import Context, Layout, Window

DATA = Path(__file__).resolve().parent / "data" / "fit_small.xplane.pb"
LAYOUT = Layout(Path(__file__).resolve().parents[2])


class _Snap:
    """The parts of a TelemetrySnapshot the readers use."""

    def __init__(self, iters, spans):
        self.events = [{"kind": "hist", "name": "engine.newton_iters",
                        "value": it, "tags": {"deg_pad": pad}}
                       for pad, it in iters.items()]
        self.spans = spans


def _ctx(snaps, window=None, red=None):
    g = inputs.grid(4, 4)
    return Context(
        cell={}, config={}, traffic={},
        window=window or Window(attempted=2, failed=0, end_to_end={}),
        trace=red if red is not None else trace.reduce_trace(str(DATA)),
        telemetry=snaps,
        work={"p": g.p, "m": g.m, "n": 512, "C": 1,
              "degrees": g.degrees(), "calls": 2,
              "want_influence": False},
        peak=work.peaks("TPU v5 lite"))


def test_rooflines_of_the_recorded_fits_are_shares():
    # the recorded trace: two fits of a 4 x 4 lattice, one bucket
    # (degrees 2 to 4 padded to 4)
    ctx = _ctx([_Snap({4: 6}, {}), _Snap({4: 6}, {})])
    bucket = LAYOUT.metric("bucket_solve_roofline_pct").read(ctx)
    score = LAYOUT.metric("score_roofline_pct").read(ctx)
    assert 0.0 < bucket < 100.0 and 0.0 < score < 100.0


def test_bucket_roofline_needs_iterations_for_every_node():
    # no bucket wide enough for the degree-4 nodes: nothing to read
    ctx = _ctx([_Snap({1: 6}, {})])
    assert LAYOUT.metric("bucket_solve_roofline_pct").read(ctx) is None
    assert LAYOUT.metric("bucket_solve_roofline_pct").read(
        _ctx([])) is None


def test_span_readers_average_per_call():
    snaps = [_Snap({}, {"fit/bucket_solve": {"total_s": 0.003},
                        "fit/combine": {"total_s": 0.010},
                        "fit": {"total_s": 0.02}}),
             _Snap({}, {"joint/admm_iter/prox_bucket_solve":
                        {"total_s": 0.005}})]
    ctx = _ctx(snaps)
    assert LAYOUT.metric("bucket_solve_ms").read(ctx) == pytest.approx(4.0)
    assert LAYOUT.metric("combine_ms").read(ctx) == pytest.approx(5.0)
    assert LAYOUT.metric("combine_ms").read(_ctx([])) is None


def test_idle_and_serve_readers():
    ctx = _ctx([], window=Window(
        attempted=3, failed=0, end_to_end={},
        samples={"queue_wait_s": [0.01, 0.02, 0.03],
                 "coalesce_sizes": [1, 2, 3]}))
    idle = LAYOUT.metric("device_idle_pct.batch").read(ctx)
    assert idle == pytest.approx(ctx.trace.idle_pct) and 0 < idle < 100
    assert LAYOUT.metric("device_idle_pct.serve").read(ctx) == idle
    assert LAYOUT.metric("queue_wait_ms").read(ctx) == pytest.approx(20.0)
    assert LAYOUT.metric("coalesce_size").read(ctx) == pytest.approx(2.0)
    assert LAYOUT.metric("queue_wait_ms").read(_ctx([])) is None
    assert np.isfinite(idle)
