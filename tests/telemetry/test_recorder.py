"""Unit tests for the telemetry core: recorders, spans, sinks, snapshots."""
import json
import os

import numpy as np
import pytest

from repro.telemetry import (NULL_RECORDER, NullRecorder, Recorder,
                             TelemetrySpec, make_recorder, read_jsonl)
from repro.telemetry.recorder import _ACTIVE, _NULL_SPAN, record_kernel_trace


# ------------------------------------------------------------------- null
def test_null_recorder_is_allocation_free():
    """The disabled path hands out ONE shared span object and never
    records anything — the zero-overhead-when-off contract."""
    assert NULL_RECORDER.enabled is False
    s1 = NULL_RECORDER.span("fit", tag=1)
    s2 = NULL_RECORDER.span("anything")
    assert s1 is s2 is _NULL_SPAN
    with s1:
        pass
    NULL_RECORDER.inc("c", 3)
    NULL_RECORDER.gauge("g", 1.0)
    NULL_RECORDER.observe("h", 2.0)
    NULL_RECORDER.point("m", 0, 1.0)
    assert NULL_RECORDER.mark() == 0
    assert NULL_RECORDER.snapshot() is None


def test_null_span_not_active_for_kernel_trace():
    with NULL_RECORDER.span("fit"):
        assert not _ACTIVE
        record_kernel_trace("kernel.x", shape=(1,))   # must be a no-op


# ------------------------------------------------------------------ spans
def test_span_paths_nest_and_aggregate():
    rec = Recorder(TelemetrySpec())
    with rec.span("fit"):
        with rec.span("bucket_solve", deg_pad=3):
            pass
        with rec.span("bucket_solve", deg_pad=5):
            pass
        with rec.span("combine", scheme="uniform"):
            pass
    snap = rec.snapshot()
    assert set(snap.spans) == {"fit", "fit/bucket_solve", "fit/combine"}
    assert snap.spans["fit/bucket_solve"]["count"] == 2
    assert snap.spans["fit"]["count"] == 1
    assert snap.spans["fit"]["total_s"] >= \
        snap.spans["fit/bucket_solve"]["total_s"]
    # stack fully unwound
    assert not rec._stack and not _ACTIVE


def test_open_span_receives_kernel_trace_events():
    rec = Recorder(TelemetrySpec())
    with rec.span("fit"):
        record_kernel_trace("kernel.test", kind="ising", shape=(2, 3))
    ev = [e for e in rec.events if e["kind"] == "event"]
    assert len(ev) == 1
    assert ev[0]["name"] == "kernel.test"
    assert ev[0]["tags"] == {"kind": "ising", "shape": (2, 3)}
    record_kernel_trace("kernel.after")               # no open span: dropped
    assert len([e for e in rec.events if e["kind"] == "event"]) == 1


def test_spans_disabled_by_spec():
    rec = Recorder(TelemetrySpec(spans=False))
    assert rec.span("fit") is _NULL_SPAN
    rec.inc("c", 1)                                   # metrics still live
    assert rec.snapshot().counters == {"c": 1}


def test_metrics_disabled_by_spec():
    rec = Recorder(TelemetrySpec(metrics=False))
    rec.inc("c", 1)
    rec.gauge("g", 2.0)
    rec.point("m", 0, 3.0)
    snap = rec.snapshot()
    assert not snap.counters and not snap.gauges and not snap.points
    with rec.span("fit"):                             # spans still live
        pass
    assert rec.snapshot().spans["fit"]["count"] == 1


# ---------------------------------------------------------------- metrics
def test_metrics_aggregate():
    rec = Recorder(TelemetrySpec())
    rec.inc("net.send", 5)
    rec.inc("net.send", 7, src=0, dst=1)
    rec.gauge("buf", 3)
    rec.gauge("buf", 9)
    rec.observe("lat", 0.5)
    rec.observe("lat", 1.5)
    rec.point("err", 1, 10.0)
    rec.point("err", 2, 4.0)
    snap = rec.snapshot()
    assert snap.counters["net.send"] == 12
    assert snap.gauges["buf"] == 9
    assert snap.histograms["lat"] == [0.5, 1.5]
    rounds, vals = snap.timeline("err")
    np.testing.assert_array_equal(rounds, [1, 2])
    np.testing.assert_array_equal(vals, [10.0, 4.0])
    with pytest.raises(KeyError, match="err"):
        snap.timeline("nope")


def test_mark_scopes_snapshot():
    rec = Recorder(TelemetrySpec())
    rec.inc("a", 1)
    mark = rec.mark()
    rec.inc("a", 10)
    assert rec.snapshot(mark).counters == {"a": 10}
    assert rec.snapshot().counters == {"a": 11}


# ------------------------------------------------------------------- sink
def test_jsonl_sink_round_trips_events(tmp_path):
    path = os.path.join(tmp_path, "sub", "trace.jsonl")
    rec = Recorder(TelemetrySpec(jsonl=path))
    with rec.span("fit", n=400):
        rec.inc("net.send", 3, src=0, dst=1)
        rec.gauge("buf", np.int64(7))                 # numpy scalars coerce
    rec.flush()
    logged = read_jsonl(path)
    assert len(logged) == len(rec.events)
    for disk, mem in zip(logged, rec.events):
        assert disk["seq"] == mem["seq"]
        assert disk["kind"] == mem["kind"]
        assert disk["name"] == mem["name"]
    # every line is standalone-parseable json
    with open(path) as f:
        for line in f:
            json.loads(line)


# ----------------------------------------------------------- make_recorder
def test_make_recorder_dispatch():
    assert make_recorder(None) is NULL_RECORDER
    assert make_recorder(False) is NULL_RECORDER
    live = Recorder(TelemetrySpec())
    assert make_recorder(live) is live                # pass-through sharing
    assert make_recorder(NULL_RECORDER) is NULL_RECORDER
    from_spec = make_recorder(TelemetrySpec())
    assert isinstance(from_spec, Recorder)
    from_dict = make_recorder({"spans": False, "metrics": True,
                               "jsonl": None})
    assert isinstance(from_dict, Recorder)
    assert from_dict.spec.spans is False
    with pytest.raises(TypeError, match="TelemetrySpec"):
        make_recorder(42)


def test_spec_round_trip_and_validation():
    spec = TelemetrySpec(spans=True, metrics=False, jsonl="/tmp/x.jsonl")
    assert TelemetrySpec.from_dict(spec.to_dict()) == spec
    with pytest.raises(TypeError):
        TelemetrySpec(jsonl=7)
    # a dict written before profile_dir was retired still loads
    assert TelemetrySpec.from_dict(
        dict(spec.to_dict(), profile_dir="/tmp/prof")) == spec


def test_null_recorder_span_is_cheap():
    """100k disabled span entries must be effectively free (generous CI
    bound — the point is catching an accidental allocation/IO path on the
    disabled branch, not microbenchmarking)."""
    import time
    t0 = time.perf_counter()
    for _ in range(100_000):
        with NULL_RECORDER.span("hot"):
            NULL_RECORDER.inc("c")
    assert time.perf_counter() - t0 < 2.0
