"""Kernel micro-benchmarks across the kernel-path taxonomy.

Every fused-CL row is timed on up to three paths and emitted as its own
metric so the perf trajectory of each tier is machine-readable
(``BENCH_kernels.json``):

* ``ref`` — the jnp reference contraction exactly as the dispatch layer's
  ``ref`` path runs it (eager, the golden oracle);
* ``compiled`` — the tier the dispatch layer picks by default: the Mosaic
  Pallas kernel on TPU, the XLA-jitted tiled twin elsewhere, with
  tiles chosen by a **measured** :func:`~repro.kernels.cl.autotune.search_tiles`
  run (the timings land in the JSON next to the winner);
* ``interpret`` — the Python-speed Pallas interpreter (validation only, so
  it is timed with one rep and skipped at the large Newton shapes).

Each compiled row also carries a FLOP/byte roofline estimate from the
loop-aware HLO walker (:mod:`repro.launch.hloparse`) over the lowered XLA
program of the tiled twin — the analyzable dot-level program on every
backend.

Two regression gates run inside the bench, not outside it: the compiled
bucket-Newton rows must beat the jnp reference on the compiled-CPU backend
(the measured ~1.4x chunked-accumulation win), and no compiled score row
may regress past ``REGRESSION_SLACK`` of its reference row."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as C
from repro.kernels.cl.autotune import search_tiles
from repro.kernels.cl.family import family_kernel_inputs
from repro.kernels.cl.kernel import cl_score_channels
from repro.kernels.cl.newton import (bucket_newton_stats,
                                     bucket_newton_stats_ref)
from repro.kernels.cl.ops import default_kernel_path
from repro.kernels.cl.ref import cl_score_channels_ref
from repro.kernels.cl.tiled import (bucket_newton_stats_tiled,
                                    cl_score_channels_tiled)
from repro.kernels.ising_cl.kernel import ising_cl_logits
from repro.kernels.ising_cl.ref import ising_cl_logits_ref, ising_cl_score_ref
from repro.kernels.ising_cl.score import ising_cl_score
from repro.kernels.gram.kernel import gram
from repro.kernels.gram.ref import gram_ref
from repro.kernels.swa.kernel import swa_attention
from repro.kernels.swa.ref import swa_attention_ref
from repro.launch.hloparse import analyze
from .util import emit, emit_json, scale

RESULTS = {}
FAMILY_RESULTS = {}
NEWTON_RESULTS = {}

#: a compiled score row slower than REGRESSION_SLACK x its reference row
#: fails the bench (the compiled tier must never cost more than timing
#: jitter over the reference it replaces at whole-axis tiles).
REGRESSION_SLACK = 1.5

#: bucket-Newton shapes where chunked accumulation is measured to win:
#: large sample axes (>= CHUNK_MIN_N), paper-scale bucket counts.
NEWTON_SHAPES = (
    ("ising", 48, 1, 5, 32768),   # kind, k, C, d, n
    ("potts", 8, 2, 9, 16384),
)


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, out


def _hlo_roofline(fn, *args, **kwargs):
    """dot FLOPs / HBM-byte estimate of the lowered XLA program via the
    loop-aware HLO walker. Best-effort: a lowering failure is recorded,
    never raised (the roofline is evidence, not a gate)."""
    try:
        txt = fn.lower(*args, **kwargs).compile().as_text()
    except Exception as e:  # pragma: no cover - backend-dependent
        return {"error": str(e)[:120]}
    h = analyze(txt)
    fpb = h["dot_flops"] / h["hbm_bytes"] if h["hbm_bytes"] else None
    return {"dot_flops": h["dot_flops"], "hbm_bytes": h["hbm_bytes"],
            "flop_per_byte": fpb}


def _record(name: str, shape_desc: str, us_ref: float, us_kernel: float,
            err: float) -> None:
    """Emit ref and interpret-path rows separately; stash for the JSON."""
    emit(f"{name}_ref", us_ref, f"{shape_desc} maxerr={err:.2e}")
    emit(f"{name}_interpret", us_kernel, f"{shape_desc} maxerr={err:.2e}")
    RESULTS[name] = {"ref_us": us_ref, "kernel_us": us_kernel,
                     "kernel_path": "interpret",
                     "shape": shape_desc, "max_err": err}


def bench_ising_cl():
    n, p = scale((512, 100), (4096, 256))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jnp.sign(jax.random.normal(ks[0], (n, p)))
    theta = 0.3 * jax.random.normal(ks[1], (p, p))
    mask = (jax.random.uniform(ks[2], (p, p)) < 0.1).astype(jnp.float32)
    bias = jnp.zeros(p)
    us_ref, ref = _time(jax.jit(ising_cl_logits_ref), x, theta, mask, bias)
    us_k, out = _time(lambda *a: ising_cl_logits(*a, interpret=True),
                      x, theta, mask, bias, reps=1)
    err = float(jnp.max(jnp.abs(out - ref)))
    _record("kernel_ising_cl", f"n={n} p={p}", us_ref, us_k, err)


def bench_ising_cl_score():
    n, p = scale((512, 100), (4096, 256))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jnp.sign(jax.random.normal(ks[0], (n, p)))
    theta = 0.3 * jax.random.normal(ks[1], (p, p))
    mask = (jax.random.uniform(ks[2], (p, p)) < 0.1).astype(jnp.float32)
    bias = 0.1 * jax.random.normal(ks[0], (p,))
    us_ref, ref = _time(jax.jit(ising_cl_score_ref), x, theta, mask, bias)
    us_k, out = _time(lambda *a: ising_cl_score(*a, interpret=True),
                      x, theta, mask, bias, reps=1)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(out, ref))
    _record("kernel_ising_cl_score", f"n={n} p={p}", us_ref, us_k, err)


def _maxerr(out, ref):
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(out, ref))


def bench_family_scores():
    """Per-family fused score rows: jnp reference vs the compiled tier vs
    the interpret-mode Pallas kernel, for every registered family. The
    compiled tier's tiles come from a measured ``search_tiles`` run whose
    timings are recorded next to the winner."""
    path = default_kernel_path()
    n, p = scale((256, 64), (2048, 256))
    side = max(int(np.sqrt(p)), 2)
    g = C.grid_graph(side, side)
    for fam in C.registered_families():
        kind = fam.kernel_kind
        theta = jnp.asarray(fam.random_params(g, jax.random.PRNGKey(23)),
                            jnp.float32)
        X = jnp.asarray(C.random_rows(fam, jax.random.PRNGKey(11), n, g.p),
                        jnp.float32)
        inputs = family_kernel_inputs(fam, g, theta, X)
        us_ref, ref = _time(
            lambda *a, _k=kind: cl_score_channels_ref(*a, kind=_k),
            *inputs, reps=5)

        def measure(cfg, _k=kind, _inputs=inputs):
            if path == "mosaic":
                fn = lambda *a: cl_score_channels(  # noqa: E731
                    *a, kind=_k, interpret=False, tiles=cfg)
            else:
                fn = lambda *a: cl_score_channels_tiled(  # noqa: E731
                    *a, kind=_k, chunk=cfg.bm)
            return _time(fn, *_inputs, reps=2)[0]

        tiles, timings = search_tiles("score", n=n, p=g.p, C=fam.block_dim,
                                      measure=measure)
        if path == "mosaic":
            comp = lambda *a, _k=kind: cl_score_channels(  # noqa: E731
                *a, kind=_k, interpret=False, tiles=tiles)
        else:
            comp = lambda *a, _k=kind: cl_score_channels_tiled(  # noqa: E731
                *a, kind=_k, chunk=tiles.bm)
        us_comp, out_c = _time(comp, *inputs, reps=5)
        us_int, out_i = _time(
            lambda *a, _k=kind: cl_score_channels(*a, kind=_k,
                                                  interpret=True),
            *inputs, reps=1)
        err_c, err_i = _maxerr(out_c, ref), _maxerr(out_i, ref)
        hlo = _hlo_roofline(cl_score_channels_tiled, *inputs, kind=kind,
                            chunk=tiles.bm)
        shape = f"C={fam.block_dim} n={n} p={g.p}"
        emit(f"kernel_cl_score_{fam.name}_ref", us_ref, shape)
        emit(f"kernel_cl_score_{fam.name}_compiled", us_comp,
             f"{shape} path={path} speedup={us_ref / us_comp:.2f}x "
             f"maxerr={err_c:.2e}")
        emit(f"kernel_cl_score_{fam.name}_interpret", us_int,
             f"{shape} maxerr={err_i:.2e}")
        FAMILY_RESULTS[fam.name] = {
            "shape": shape, "block_dim": fam.block_dim,
            "kernel_kind": kind,
            "rows": {
                "ref": {"us": us_ref, "kernel_path": "ref"},
                "compiled": {"us": us_comp, "kernel_path": path,
                             "max_err": err_c,
                             "speedup_vs_ref": us_ref / us_comp,
                             "tiles": tiles.to_dict(),
                             "search_timings_us": timings, "hlo": hlo},
                "interpret": {"us": us_int, "kernel_path": "interpret",
                              "max_err": err_i},
            },
        }


def bench_bucket_newton():
    """Compiled bucket-Newton vs the jitted jnp reference at the shapes
    where chunked Gram accumulation is measured to win (large sample axes).
    Tiles come from a measured search; on the compiled-CPU backend the
    compiled row MUST beat the reference — asserted, not just reported."""
    path = default_kernel_path()
    for kind, k, Cc, d, n in NEWTON_SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(hash(kind) % 2 ** 31), 5)
        Zb = jax.random.normal(ks[0], (k, Cc, d, n))
        base = 0.1 * jax.random.normal(ks[1], (k, Cc, n))
        if kind == "potts":
            xi = jax.random.randint(ks[2], (k, n), 0, Cc + 1) \
                .astype(jnp.float32)
        else:
            xi = jnp.sign(jax.random.normal(ks[2], (k, n)))
        W = 0.2 * jax.random.normal(ks[3], (k, d * Cc))

        us_ref, ref = _time(
            lambda *a, _k=kind: bucket_newton_stats_ref(_k, *a),
            Zb, base, xi, W, reps=5)

        def measure(cfg, _k=kind, _a=(Zb, base, xi, W)):
            if path == "mosaic":
                fn = lambda *a: bucket_newton_stats(  # noqa: E731
                    _k, *a, interpret=False, tiles=cfg)
            else:
                fn = lambda *a: bucket_newton_stats_tiled(  # noqa: E731
                    _k, *a, chunk=cfg.bm)
            return _time(fn, *_a, reps=2)[0]

        tiles, timings = search_tiles(
            "newton", n=n, p=d, C=Cc, measure=measure,
            k=k if path == "mosaic" else None)
        if path == "mosaic":
            comp = lambda *a, _k=kind: bucket_newton_stats(  # noqa: E731
                _k, *a, interpret=False, tiles=tiles)
        else:
            comp = lambda *a, _k=kind: bucket_newton_stats_tiled(  # noqa: E731
                _k, *a, chunk=tiles.bm)
        us_comp, out = _time(comp, Zb, base, xi, W, reps=5)
        err = _maxerr(out, ref)
        speedup = us_ref / us_comp
        hlo = _hlo_roofline(bucket_newton_stats_tiled, kind, Zb, base, xi,
                            W, chunk=tiles.bm)
        shape = f"k={k} C={Cc} d={d} n={n}"
        emit(f"kernel_newton_{kind}_ref", us_ref, shape)
        emit(f"kernel_newton_{kind}_compiled", us_comp,
             f"{shape} path={path} speedup={speedup:.2f}x "
             f"maxerr={err:.2e}")
        NEWTON_RESULTS[kind] = {
            "shape": shape, "ref_us": us_ref, "compiled_us": us_comp,
            "speedup_vs_ref": speedup, "kernel_path": path,
            "max_err": err, "tiles": tiles.to_dict(),
            "search_timings_us": timings, "hlo": hlo,
        }

    if path == "tiled":
        best = max(r["speedup_vs_ref"] for r in NEWTON_RESULTS.values())
        assert best > 1.0, (
            f"compiled bucket-Newton must beat the jnp reference on the "
            f"compiled-CPU backend; best speedup was {best:.2f}x "
            f"({ {k: round(r['speedup_vs_ref'], 2) for k, r in NEWTON_RESULTS.items()} })")


def bench_gram():
    n, d = scale((2048, 128), (16384, 512))
    s = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    us_ref, ref = _time(jax.jit(gram_ref), s)
    us_k, out = _time(lambda a: gram(a, interpret=True), s, reps=1)
    err = float(jnp.max(jnp.abs(out - ref)))
    _record("kernel_gram", f"n={n} d={d}", us_ref, us_k, err)


def bench_swa():
    b, s, h, d, w = 1, scale(256, 1024), 4, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    us_ref, ref = _time(jax.jit(
        lambda q, k, v: swa_attention_ref(q, k, v, window=w)), q, k, v)
    us_k, out = _time(lambda q, k, v: swa_attention(q, k, v, window=w,
                                                    interpret=True),
                      q, k, v, reps=1)
    err = float(jnp.max(jnp.abs(out - ref)))
    _record("kernel_swa", f"s={s} window={w}", us_ref, us_k, err)


def main() -> None:
    bench_ising_cl()
    bench_ising_cl_score()
    bench_family_scores()
    bench_bucket_newton()
    bench_gram()
    bench_swa()
    for fam, rec in FAMILY_RESULTS.items():
        rows = rec["rows"]
        assert rows["compiled"]["us"] <= REGRESSION_SLACK * rows["ref"]["us"], (
            f"compiled score row for {fam} regressed past "
            f"{REGRESSION_SLACK}x the reference: "
            f"{rows['compiled']['us']:.0f}us vs {rows['ref']['us']:.0f}us")
    emit_json("BENCH_kernels.json", {
        "backend": jax.default_backend(),
        "kernel_path": default_kernel_path(),
        "kernels": RESULTS,
        "families": FAMILY_RESULTS,
        "newton": NEWTON_RESULTS,
    })


if __name__ == "__main__":
    main()
