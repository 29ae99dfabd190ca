"""The bucket solve's sandwich variance and its large-bucket Newton loop.

The diagonal combiner weighs each owner by 1 / V_aa, and on a lattice the
sandwich V = H^-1 J H^-1 of a site whose neighbours nearly determine it
multiplies every rounding in H and J by a condition number in the
hundreds. So the solve forms its curvature without cancellation, from an
exp made of float32 multiplies and adds, sums H and J with compensation,
and leaves the inverse to the host in float64. A bucket of more than
``_SPLIT_ROWS`` rows finishes its slowest rows on their own.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched
from repro.core.batched import _gram_hilo, fit_all_local_batched
from repro.core.families import IsingFamily
from repro.core.families.ising import exp_neg
from repro.core.graphs import Graph

from bench import inputs, reference

ULP32 = float(np.finfo(np.float32).eps)


@pytest.mark.parametrize("lo,hi", [(0.0, 1.0), (1.0, 20.0), (20.0, 80.0)])
def test_exp_neg_is_within_two_float32_ulps(lo, hi):
    a = np.linspace(lo, hi, 100_001, dtype=np.float32)
    got = np.asarray(exp_neg(jnp.asarray(a)), np.float64)
    want = np.exp(-a.astype(np.float64))
    assert np.max(np.abs(got / want - 1.0)) <= 2 * ULP32


def test_exp_neg_of_float64_is_exp():
    a = np.linspace(0.0, 50.0, 1001)
    with jax.enable_x64(True):
        got = np.asarray(exp_neg(jnp.asarray(a, jnp.float64)))
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, np.exp(-a), rtol=1e-15)


def test_ising_sandwich_terms_match_float64():
    rng = np.random.default_rng(0)
    eta = rng.normal(0.0, 4.0, (3, 1, 4096)).astype(np.float32)
    xi = np.where(rng.random((3, 4096)) < 0.5, -1.0, 1.0).astype(np.float32)
    r, kap = IsingFamily().sandwich_terms(jnp.asarray(eta), jnp.asarray(xi))
    e, x = eta[:, 0].astype(np.float64), xi.astype(np.float64)
    s = 1.0 / (1.0 + np.exp(2.0 * x * e))              # sigma(-2 x eta)
    t = np.exp(-2.0 * np.abs(e))
    want_r = 2.0 * x * s
    want_kap = 4.0 * t / (1.0 + t) ** 2                # no 1 - s in float64

    assert r.shape == (3, 1, 4096) and kap.shape == (3, 1, 1, 4096)
    assert np.max(np.abs(np.asarray(r[:, 0], np.float64) / want_r - 1)) \
        <= 4 * ULP32
    assert np.max(np.abs(np.asarray(kap[:, 0, 0], np.float64) / want_kap
                         - 1)) <= 8 * ULP32
    # the hooks' form r (2 x - r) loses digits where the curvature is small
    old = np.asarray(IsingFamily().curvature(jnp.asarray(eta),
                                             jnp.asarray(xi))[:, 0, 0])
    assert np.max(np.abs(old / want_kap - 1)) > 100 * ULP32


@pytest.mark.parametrize("n", [8192, 1000, 384])
def test_gram_hilo_holds_the_sum_to_an_ulp(n):
    """hi + lo matches the float64 sum of the float32 products, where one
    float32 contraction misses by far more; 1000 pads the last block and
    384 makes an odd number of blocks."""
    rng = np.random.default_rng(n)
    Z = np.where(rng.random((4, 5, n)) < 0.5, -1.0, 1.0).astype(np.float32)
    w = rng.random((4, n)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_gram_hilo(jnp.asarray(Z), jnp.asarray(w)))
    assert got.shape == (4, 2, 5, 5)
    want = np.einsum("kan,kbn,kn->kab", Z.astype(np.float64),
                     Z.astype(np.float64), w.astype(np.float64))
    err = np.abs(got[:, 0].astype(np.float64) + got[:, 1] - want)
    # each entry is a signed sum of terms of total weight sum_n w
    scale = w.astype(np.float64).sum(axis=1)[:, None, None]
    assert np.max(err / scale) <= ULP32
    plain = np.einsum("kan,kbn->kab", Z * w[:, None, :], Z)
    assert np.max(err / scale) < np.max(np.abs(plain - want) / scale)
    np.testing.assert_array_equal(got[:, 0], got[:, 0].swapaxes(1, 2))


def _lattice(rows, n, seed):
    """The benchmark lattice's sampler (theta ~ N(0, 0.5^2), 200 Gibbs
    sweeps), cut to rows x rows sites."""
    cfg = json.loads((Path(reference.__file__).parent / "configs"
                      / "lattice64.json").read_text())
    cfg.update(rows=rows, cols=rows, p=rows * rows)
    bg = inputs.build_graph(cfg)
    _, (X,) = inputs.sample_sets("ising", bg, seed, cfg, 1, n)
    return Graph(bg.p, tuple(bg.edges)), X


def test_lattice_variances_match_the_float64_reference():
    """At the lattice's most ill-conditioned sites the variance diagonals
    agree with the float64 reference to 5e-5; a float32 sandwich (sums
    over n, then a float32 inverse) misses by 1e-4 and more there."""
    g, X = _lattice(16, 8192, 3500000016)
    fits = fit_all_local_batched(g, X, want_influence=False)
    ref = reference.local_fits("ising", np.asarray(X, np.float64), g.p,
                               g.edges)
    got = np.concatenate([np.diag(f.V) for f in fits]).astype(np.float64)
    want = np.concatenate([ref[f.i]["vdiag"] for f in fits])
    assert want.max() > 50.0                     # ill-conditioned sites
    assert np.max(np.abs(got / want - 1.0)) <= 5e-5
    for f in fits:
        assert f.V.dtype == f.H.dtype == f.J.dtype == np.float32
        np.testing.assert_allclose(f.V, f.V.T, rtol=1e-6, atol=0)


def test_split_bucket_matches_the_whole_bucket(monkeypatch):
    """A bucket above ``_SPLIT_ROWS`` rows finishes its slowest rows alone:
    the estimates agree with iterating the whole bucket to Newton
    tolerance, and a slow site still gets the full budget."""
    g, X = _lattice(12, 2048, 3500000003)
    X = np.array(X)
    X[:-3, 40] = 1.0            # node 40 almost always +1: a slow local fit
    X = jnp.asarray(X)

    def fit(split_rows):
        monkeypatch.setattr(batched, "_SPLIT_ROWS", split_rows)
        batched.clear_bucket_solver_caches()
        stats = {}
        fits = fit_all_local_batched(g, X, want_influence=False)
        return fits, stats

    (whole, _), (split, _) = fit(10**9), fit(100)
    batched.clear_bucket_solver_caches()
    assert len(batched.degree_buckets(g)) == 1 and g.p > 100
    for a, b in zip(whole, split):
        assert a.beta == b.beta
        np.testing.assert_allclose(b.theta, a.theta, rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.diag(b.V), np.diag(a.V), rtol=1e-3)
    # the slow site moved far from zero: it ran past the bulk's iterations
    assert np.abs(split[40].theta[0]) > 2.0
