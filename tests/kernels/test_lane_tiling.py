"""Edge-tile invariants for the fused CL kernels.

The acceptance contract of the tiling: zero padding is provably
invisible —

* the bucket Newton kernel's sample tile need not divide n (padded design
  columns are zero, so every contribution vanishes term-by-term), for
  every family epilogue, weighted or not;
* the ``(j, i, k)`` score-kernel grid handles shapes that do NOT divide
  the tile sizes (edge tiles) exactly like shapes that do.

Both are pinned as hypothesis properties against the jnp references.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.cl.autotune import TileConfig  # noqa: E402
from repro.kernels.cl.kernel import cl_score_channels  # noqa: E402
from repro.kernels.cl.newton import (bucket_newton_stats,  # noqa: E402
                                     bucket_newton_stats_ref)
from repro.kernels.cl.ref import cl_score_channels_ref  # noqa: E402


# ------------------------------------- sample edge tiles invisible (g, K)
def _newton_case(kind, k, C, d, n, seed, weighted):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    Zb = jax.random.normal(ks[0], (k, C, d, n))
    base = 0.1 * jax.random.normal(ks[1], (k, C, n))
    if kind == "potts":
        xi = jax.random.randint(ks[2], (k, n), 0, C + 1).astype(jnp.float32)
    elif kind == "gaussian":
        xi = jax.random.normal(ks[2], (k, n))
    else:
        xi = jnp.sign(jax.random.normal(ks[2], (k, n)))
    W = 0.2 * jax.random.normal(ks[3], (k, d * C))
    sw = jax.random.uniform(ks[4], (k, n)) if weighted else None
    return Zb, base, xi, W, sw


def _check_newton(kind, C, k, d, n, bm, weighted, seed):
    Zb, base, xi, W, sw = _newton_case(kind, k, C, d, n, seed, weighted)
    g0, K0 = bucket_newton_stats_ref(kind, Zb, base, xi, W, sw)
    g1, K1 = bucket_newton_stats(kind, Zb, base, xi, W, sw, interpret=True,
                                 tiles=TileConfig(bm=bm))
    assert g1.shape == g0.shape and K1.shape == K0.shape
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=2e-5)
    np.testing.assert_allclose(np.asarray(K1), np.asarray(K0), atol=2e-5)


@given(d=st.integers(1, 6), n=st.integers(1, 50),
       bm=st.sampled_from([8, 16, 32]),
       weighted=st.booleans(), seed=st.integers(0, 2 ** 16))
@settings(max_examples=12, deadline=None)
def test_newton_edge_tiles_match_ref_potts(d, n, bm, weighted, seed):
    """Interpret-mode bucket Newton with a sample tile that does not
    divide n == the jnp reference (multi-channel, channel-major blocks
    permuted back to the coordinate-major flat layout)."""
    _check_newton("potts", 2, 2, d, n, bm, weighted, seed)


@given(d=st.integers(1, 8), n=st.integers(1, 60),
       bm=st.sampled_from([8, 16, 128]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=12, deadline=None)
def test_newton_edge_tiles_match_ref_ising(d, n, bm, seed):
    """Single-channel path under sample edge tiles."""
    _check_newton("ising", 1, 3, d, n, bm, False, seed)


@given(d=st.integers(1, 8), n=st.integers(1, 60),
       bm=st.sampled_from([8, 16, 128]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=12, deadline=None)
def test_newton_edge_tiles_match_ref_gaussian_weighted(d, n, bm, seed):
    """Constant-curvature epilogue with per-node sample weights."""
    _check_newton("gaussian", 1, 3, d, n, bm, True, seed)


# ------------------------------------------------- score-kernel edge tiles
@given(n=st.integers(1, 40), p=st.integers(2, 11),
       tiles=st.sampled_from([TileConfig(bm=8, bn=8, bk=8),
                              TileConfig(bm=16, bn=8, bk=16),
                              TileConfig(bm=32, bn=16, bk=8)]),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_score_kernel_edge_tiles_match_ref(n, p, tiles, seed):
    """The (j, i, k) score grid with tiles that do NOT divide (n, p) — and
    bn != bk, so the p-pad is the lcm — equals the reference exactly up to
    float32 jitter, multi-channel epilogue included."""
    from repro.kernels.cl.epilogues import get_epilogue
    C = 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.randint(ks[0], (n, p), 0, C + 1).astype(jnp.float32)
    F = get_epilogue("potts").features(x, C)
    theta = 0.3 * jax.random.normal(ks[1], (C, p, p))
    mask = jnp.ones((p, p)) - jnp.eye(p)
    bias = 0.1 * jax.random.normal(ks[2], (C, p))
    ref = cl_score_channels_ref(F, theta, mask, bias, kind="potts")
    out = cl_score_channels(F, theta, mask, bias, kind="potts",
                            interpret=True, tiles=tiles)
    for o, r in zip(out, ref):
        assert o.shape == r.shape
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)
