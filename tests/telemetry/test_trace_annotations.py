"""Live spans on the profiler's clock, and the device-to-host counter.

Every live span is a ``jax.profiler.TraceAnnotation`` named by its path, so
a profiler trace taken around a verb shows the program's layers on the
host thread that called it; with telemetry off no annotation is built.
``engine.d2h_bytes`` counts each fetch from the device on the estimate
path: one increment per host round trip, valued at the device arrays'
bytes.
"""
import glob
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.api as A
from repro.core.batched import degree_buckets
from repro.core.graphs import (chain_graph, grid_graph, scale_free_graph,
                               star_graph)
from repro.telemetry import Recorder, TelemetrySpec
from repro.telemetry.recorder import _ACTIVE, D2H_BYTES

SCHEMES = ("uniform", "diagonal")


def _pm1(n, p, seed=0):
    rng = np.random.RandomState(seed)
    return rng.choice([-1.0, 1.0], size=(n, p)).astype(np.float32)


class _Counting(jax.profiler.TraceAnnotation):
    """A TraceAnnotation that counts constructions and exits."""
    built = 0
    exits = []

    def __init__(self, name, **kwargs):
        type(self).built += 1
        super().__init__(name, **kwargs)

    def __exit__(self, *exc):
        type(self).exits.append(exc[0])
        return super().__exit__(*exc)


@pytest.fixture
def counting(monkeypatch):
    monkeypatch.setattr(_Counting, "built", 0)
    monkeypatch.setattr(_Counting, "exits", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    return _Counting


def _host_events(trace_dir):
    """(start, end, name, stats) of the host line that holds ``fit``."""
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats)) for ev in line.events]
            if any(name == "fit" for _, _, name, _ in evs):
                return evs
    raise AssertionError("no host line holds a 'fit' annotation")


def test_fit_annotations_nest_on_the_calling_thread(tmp_path):
    g = star_graph(5)
    X = _pm1(200, g.p)
    sess = A.Plan(graph=g, combiners=SCHEMES,
                  telemetry=TelemetrySpec()).session()
    sess.fit(X)                                     # compile outside
    with jax.profiler.trace(str(tmp_path)):
        sess.fit(X)
    evs = [e for e in _host_events(tmp_path)
           if e[2] == "fit" or e[2].startswith("fit/")]
    names = {name for _, _, name, _ in evs}
    assert names == {"fit", "fit/bucket_prep", "fit/bucket_solve",
                     "fit/assemble", "fit/combine", "fit/score",
                     "fit/score/score_fetch"} | {
        f"fit/combine/{s}" for s in SCHEMES}
    (fit,) = [e for e in evs if e[2] == "fit"]
    call = fit[3]["call"]
    for a, b, name, stats in evs:
        assert stats["call"] == call            # one verb, one group
        parent = name.rsplit("/", 1)[0] if "/" in name else None
        if parent is not None:
            # inside an annotation of its parent path on the same line
            assert any(pa <= a and b <= pb for pa, pb, pn, _ in evs
                       if pn == parent), name
    # the span's tags ride along as the annotation's stats
    solves = [s for _, _, n, s in evs if n == "fit/bucket_solve"]
    assert sorted((s["deg_pad"], s["k"]) for s in solves) == sorted(
        (b.deg_pad, len(b.nodes)) for b in degree_buckets(g))
    assert sorted(s["scheme"] for _, _, n, s in evs
                  if n == "fit/combine") == sorted(SCHEMES)


def test_span_events_carry_id_and_parent():
    rec = Recorder(TelemetrySpec())
    with rec.span("fit"):
        with rec.span("combine", scheme="uniform"):
            with rec.span("uniform"):
                pass
    ends = {e["name"]: e for e in rec.events if e["kind"] == "span_end"}
    starts = {e["name"]: e for e in rec.events if e["kind"] == "span_start"}
    for path, ev in ends.items():
        assert ev["id"] == starts[path]["id"] == starts[path]["seq"]
        assert ev["parent"] == starts[path]["parent"]
    assert ends["fit"]["parent"] is None
    assert ends["fit/combine"]["parent"] == ends["fit"]["id"]
    assert ends["fit/combine/uniform"]["parent"] == ends["fit/combine"]["id"]
    assert rec.snapshot().spans["fit/combine/uniform"]["count"] == 1


def test_raising_span_closes_its_annotation(counting):
    rec = Recorder(TelemetrySpec())
    with pytest.raises(ValueError, match="boom"):
        with rec.span("fit"):
            with rec.span("combine", scheme="x"):
                raise ValueError("boom")
    assert counting.built == 2
    assert counting.exits == [ValueError, ValueError]
    assert not rec._stack and not rec._ids and not _ACTIVE
    assert [e["name"] for e in rec.events if e["kind"] == "span_end"] == [
        "fit/combine", "fit"]


def test_telemetry_off_builds_no_annotation(counting):
    g = chain_graph(4)
    X = _pm1(100, g.p)
    off = A.Plan(graph=g, combiners=SCHEMES).session()
    off.fit(X)
    off.joint(X)
    assert counting.built == 0
    on = A.Plan(graph=g, combiners=SCHEMES,
                telemetry=TelemetrySpec()).session()
    res = on.fit(X)
    starts = [e for e in res.telemetry.events if e["kind"] == "span_start"]
    assert counting.built == len(starts) > 0


def test_d2h_bytes_match_the_fleet_shapes():
    # the paper's fleet: 100-sensor Barabasi-Albert (m = 1), n = 4000,
    # Ising, a combiner that reads the per-sample influence stacks
    g = scale_free_graph(100, m=1, seed=0)
    n = 4000
    res = A.Plan(graph=g, combiners=("optimal",),
                 telemetry=TelemetrySpec()).session().fit(_pm1(n, g.p))
    f32 = 4
    want = []
    for b in degree_buckets(g):
        k, d = len(b.nodes), b.deg_pad + 1
        # W (k, d); H, J as compensated pairs (k, 2, d, d); S (k, n, d);
        # Newton iterations (k,); sample counts (k,)
        want.append(f32 * (k * d + 4 * k * d * d + k * n * d) + 4 * k
                    + f32 * k)
    want.append(f32 * (n * g.p + g.p * g.p))       # score: r (1, n, p), Gram
    got = [e for e in res.telemetry.events
           if e["kind"] == "counter" and e["name"] == D2H_BYTES]
    assert [e["value"] for e in got] == want
    assert [e["tags"]["site"] for e in got] == \
        ["bucket_solve"] * (len(want) - 1) + ["score"]
    assert res.telemetry.counters[D2H_BYTES] == sum(want)


def test_joint_spans_and_prox_fetches():
    g = chain_graph(5)
    iters = 2
    res = A.Plan(graph=g, combiners=("diagonal",), admm_iters=iters,
                 telemetry=TelemetrySpec()).session().joint(_pm1(150, g.p))
    snap = res.telemetry
    n_buckets = len(degree_buckets(g))
    assert snap.spans["joint/admm_iter/bucket_prep"]["count"] == \
        snap.spans["joint/admm_iter/prox_bucket_solve"]["count"] \
        == iters * n_buckets
    assert snap.spans["joint/score"]["count"] == 1
    assert snap.counter(D2H_BYTES, site="prox_bucket_solve") > 0
    assert snap.counter(D2H_BYTES, site="score") > 0


def test_score_fetch_span_holds_the_score_fetch():
    """The score's fetch to the host, and its counter increment, sit in a
    ``score_fetch`` span inside ``score``; the benchmark's reader of that
    span reads it per call."""
    from bench.harness import Context, Layout, Window

    g = grid_graph(6, 6)
    n = 512
    sess = A.Plan(graph=g, combiners=("diagonal",),
                  telemetry=TelemetrySpec()).session()
    snaps = [sess.fit(_pm1(n, g.p, seed)).telemetry for seed in (1, 2)]
    for snap in snaps:
        fetch = snap.spans["fit/score/score_fetch"]
        assert fetch["count"] == 1
        assert 0.0 < fetch["total_s"] <= snap.spans["fit/score"]["total_s"]
        (start,) = [e for e in snap.events if e["kind"] == "span_start"
                    and e["name"] == "fit/score/score_fetch"]
        (end,) = [e for e in snap.events if e["kind"] == "span_end"
                  and e["name"] == "fit/score/score_fetch"]
        (score,) = [e for e in snap.events if e["kind"] == "counter"
                    and e["name"] == D2H_BYTES
                    and e["tags"]["site"] == "score"]
        assert start["seq"] < score["seq"] < end["seq"]
        # the bytes counted are the same: r (1, n, p) and the Gram (p, p)
        assert score["value"] == 4 * (n * g.p + g.p * g.p)
        # W, the compensated H and J pairs, the Newton counts and sample
        # counts of each bucket; the diagonal combiner reads no influence
        # stacks, so S comes back (k, 0, d)
        buckets = sum(4 * (k * d + 4 * k * d * d) + 4 * k + 4 * k
                      for k, d in ((len(b.nodes), b.deg_pad + 1)
                                   for b in degree_buckets(g)))
        assert snap.counters[D2H_BYTES] == buckets + score["value"]

    layout = Layout(Path(__file__).resolve().parents[2])
    ctx = Context(cell={}, config={}, traffic={},
                  window=Window(attempted=2, failed=0, end_to_end={}),
                  trace=None, telemetry=snaps, work={}, peak=None)
    got = layout.metric("score_fetch_ms").read(ctx)
    assert got == pytest.approx(1e3 * np.mean(
        [s.spans["fit/score/score_fetch"]["total_s"] for s in snaps]))
    assert got > 0.0


def test_joint_score_has_its_fetch_span():
    res = A.Plan(graph=chain_graph(5), combiners=("diagonal",),
                 admm_iters=2, telemetry=TelemetrySpec()
                 ).session().joint(_pm1(150, 5))
    assert res.telemetry.spans["joint/score/score_fetch"]["count"] == 1
