"""Linear-Opt's influence columns are gathered, not padded.

``Combiner.combine`` hands a combiner that needs ``"influence"`` the
(P, k, n) float64 columns ``s[:, pos]`` of each owner group. They used to be
read out of a dense zero-padded (p, n, dmax) float64 stack built on every
call; they are now gathered from the fits directly. The stack survives here
only as the reference: the gathered columns, their Gram and the combined
theta must equal it bit for bit on every case below, and the memory guard
keeps the stack from coming back.
"""
import dataclasses
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro.core import combiners
from repro.core.asymptotics import param_owners
from repro.core.batched import degree_buckets, fit_all_local_batched
from repro.core.combiners import OPTIMAL, TRUST_RADIUS

N = 400


def _dense_pad_columns(fit_of, node, pos, n):
    """The former driver's columns: zero-pad every fit's ``s`` into one
    (p, n, dmax) float64 stack, then read ``stack[node, :, pos]``."""
    p = max(max(fit_of) + 1, int(node.max()) + 1)
    dmax = max(len(f.theta) for f in fit_of.values())
    stack = np.zeros((p, n, dmax), dtype=np.float64)
    for f in fit_of.values():
        stack[f.i, :, :len(f.theta)] = f.s
    return stack[node, :, pos]


def _data(fam, p, seed):
    rng = np.random.RandomState(seed)
    if fam.name == "ising":
        X = np.where(rng.rand(N, p) < 0.5, -1.0, 1.0)
    elif fam.name == "gaussian":
        X = rng.randn(N, p)
    else:
        X = rng.randint(0, fam.q, size=(N, p)).astype(np.float64)
    return jnp.asarray(X, jnp.float32)


@pytest.fixture(scope="module")
def hub_graph():
    g = C.scale_free_graph(30, 1, 0)
    degs = np.bincount(np.array(g.edges).ravel(), minlength=g.p)
    # a hub, and degrees that fall in more than one bucket of the engine
    assert degs.max() >= 6 and len(degree_buckets(g)) >= 2
    return g


def _fit(g, fam, include_singleton=True, weighted=False, seed=0):
    kw = dict(family=fam, include_singleton=include_singleton)
    if weighted:
        kw["sample_weight"] = jnp.asarray(
            (np.arange(N)[None, :] < (150 + 7 * np.arange(g.p))[:, None])
            .astype(np.float32))
    return fit_all_local_batched(g, _data(fam, g.p, seed), **kw)


def _diverge(fits, node, value):
    fits = list(fits)
    f = fits[node]
    theta = f.theta.copy()
    theta[-1] = value
    fits[node] = dataclasses.replace(f, theta=theta)
    return fits


def _truncate(fits, node):
    """A fit whose block is one parameter short of its beta."""
    fits = list(fits)
    f = fits[node]
    d = len(f.theta) - 1
    fits[node] = dataclasses.replace(
        f, theta=f.theta[:d], V=f.V[:d, :d], s=f.s[:, :d])
    return fits


def _case(name, g):
    """(graph, fits, include_singleton, family) of one named case."""
    ising = C.get_family("ising")
    degs = np.bincount(np.array(g.edges).ravel())
    hub = int(np.argmax(degs))
    # a node below the hub's degree: dropping or cutting its fit leaves
    # dmax, and so the (p, dmax) estimate stacks, as they were
    mid = int(np.flatnonzero((degs > 1) & (degs < degs.max()))[0])
    if name == "ising_ba_hub":
        return g, _fit(g, ising), True, ising
    if name == "gaussian":
        fam = C.get_family("gaussian")
        return g, _fit(g, fam, seed=1), True, fam
    if name == "potts_block_dim_2":
        fam = C.get_family("potts")
        assert fam.block_dim == 2
        return g, _fit(g, fam, seed=2), True, fam
    if name == "no_singleton":
        return g, _fit(g, ising, include_singleton=False), False, ising
    if name == "shuffled":
        fits = _fit(g, ising)
        order = np.random.RandomState(3).permutation(len(fits))
        return g, [fits[i] for i in order], True, ising
    if name == "diverged_nan":
        return g, _diverge(_fit(g, ising), hub, np.nan), True, ising
    if name == "diverged_trust_radius":
        return (g, _diverge(_fit(g, ising), hub, 2 * TRUST_RADIUS), True,
                ising)
    if name == "weighted":
        fits = _fit(g, ising, weighted=True)
        # masked rows carry no influence: the columns hold zeros there
        assert np.all(fits[0].s[150:] == 0) and np.any(fits[0].s[:150])
        return g, fits, True, ising
    if name == "missing_fit":
        fits = _fit(g, ising)
        return g, fits[:mid] + fits[mid + 1:], True, ising
    if name == "truncated_block":
        return g, _truncate(_fit(g, ising), mid), True, ising
    raise KeyError(name)


CASES = ["ising_ba_hub", "gaussian", "potts_block_dim_2", "no_singleton",
         "shuffled", "diverged_nan", "diverged_trust_radius", "weighted",
         "missing_fit", "truncated_block"]


@pytest.mark.parametrize("name", CASES)
def test_optimal_gathered_columns_match_dense_pad(name, hub_graph,
                                                  monkeypatch):
    g, fits, inc, fam = _case(name, hub_graph)
    n = fits[0].s.shape[0]
    fit_of = {f.i: f for f in fits}
    groups = combiners._owner_groups(param_owners(g, inc, fam))
    shared = [(node, pos) for k, (_, node, pos) in groups.items() if k >= 2]
    assert shared
    for node, pos in shared:
        got = combiners._influence_columns(fit_of, node, pos, n)
        want = _dense_pad_columns(fit_of, node, pos, n)
        assert got.dtype == np.float64 and got.flags.c_contiguous
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got @ got.transpose(0, 2, 1),
                                      want @ want.transpose(0, 2, 1))

    theta = OPTIMAL.combine(g, fits, inc, family=fam)
    monkeypatch.setattr(combiners, "_influence_columns", _dense_pad_columns)
    ref = OPTIMAL.combine(g, fits, inc, family=fam)
    assert np.all(np.isfinite(theta))
    np.testing.assert_allclose(theta, ref, rtol=0, atol=1e-12)
    np.testing.assert_array_equal(theta, ref)   # same layout: bit for bit


def _synthetic_star_fits(p, n, seed=0):
    """Float32 fits on a star: the hub's block sets dmax = p."""
    g = C.star_graph(p)
    rng = np.random.RandomState(seed)
    fits = []
    for i in range(p):
        beta = g.beta(i, True)
        d = len(beta)
        A = rng.randn(d, d)
        fits.append(C.LocalFit(
            i=i, beta=list(beta), theta=0.3 * rng.randn(d), H=np.eye(d),
            J=np.eye(d), V=A @ A.T + np.eye(d),
            s=rng.randn(n, d).astype(np.float32)))
    return g, fits


def _peak_bytes(fn):
    fn()                                  # warm caches outside the window
    tracemalloc.start()
    try:
        fn()
        return tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()


def test_optimal_combine_allocates_no_dense_influence_stack(monkeypatch):
    p, n = 24, 2000
    g, fits = _synthetic_star_fits(p, n)
    dmax = max(len(f.theta) for f in fits)
    sum_d = sum(len(f.theta) for f in fits)
    assert p * dmax >= 5 * sum_d
    shared_cols = 2 * g.m                                   # P * k
    budget = 3 * (shared_cols * n * 8 + sum_d * n * 4)

    peak = _peak_bytes(lambda: OPTIMAL.combine(g, fits))
    assert peak < budget, (peak, budget)

    # the bound tells the two apart: the former dense stack breaks it
    monkeypatch.setattr(combiners, "_influence_columns", _dense_pad_columns)
    assert _peak_bytes(lambda: OPTIMAL.combine(g, fits)) > budget
