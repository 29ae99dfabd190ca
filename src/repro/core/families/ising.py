"""Ising family: the seed model re-expressed as a :class:`ModelFamily`.

Single-channel (C = 1) logistic node conditionals over x in {-1, +1}; the
flat layout and all model math delegate to :mod:`repro.core.ising`, so the
family instance and the seed code paths agree exactly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs import Graph
from .. import ising as I
from .base import ModelFamily


_LOG2E = 1.4426950408889634
_LN2_HI = 0.693145751953125           # 16 significant bits: k * _LN2_HI is exact
_LN2_LO = 1.42860682030941723212e-6


def exp_neg(a):
    """exp(-a) for a >= 0, to about an ulp in float32 on every backend.

    The TPU's float32 ``exp`` is off by up to 5e-6 relative, which the
    sandwich variance of an ill-conditioned fit multiplies by its condition
    number; this form uses only float32 multiplies and adds: a Cody-Waite
    reduction a = k ln 2 + f with |f| <= ln 2 / 2, then a degree-9 Taylor
    polynomial of exp(-f), scaled by 2^-k through the exponent bits.
    float64 input takes ``jnp.exp``. Results below exp(-80) are exp(-80).
    """
    if a.dtype == jnp.float64:
        return jnp.exp(-a)
    a = jnp.minimum(a, 80.0)
    k = jnp.round(a * _LOG2E)
    f = k * _LN2_HI - a
    f = f + k * _LN2_LO               # f = -(a - k ln 2), in [-0.35, 0.35]
    poly = jnp.full_like(f, 1.0 / 362880.0)
    for c in (1 / 40320.0, 1 / 5040.0, 1 / 720.0, 1 / 120.0, 1 / 24.0,
              1 / 6.0, 0.5, 1.0, 1.0):
        poly = poly * f + c
    scale = jax.lax.bitcast_convert_type(
        (127 - k.astype(jnp.int32)) << 23, jnp.float32)
    return poly * scale


@dataclasses.dataclass(frozen=True)
class IsingFamily(ModelFamily):
    name: str = "ising"

    @property
    def kernel_kind(self) -> str:
        return "ising"

    @property
    def block_dim(self) -> int:
        return 1

    # ----------------------------------------------------- channel hooks
    def edge_features(self, x):
        return jnp.asarray(x)[..., None]

    def loglik_eta(self, eta, xi):
        return jax.nn.log_sigmoid(2.0 * xi * eta[..., 0, :])

    def dl_deta(self, eta, xi):
        r = 2.0 * xi * jax.nn.sigmoid(-2.0 * xi * eta[..., 0, :])
        return r[..., None, :]

    def curvature(self, eta, xi):
        r = 2.0 * xi * jax.nn.sigmoid(-2.0 * xi * eta[..., 0, :])
        kap = r * (2.0 * xi - r)      # = 4 sigma(2 eta) sigma(-2 eta)
        return kap[..., None, None, :]

    def sandwich_terms(self, eta, xi):
        # Both terms from t = exp(-2|eta|): sigma(-2 x eta) is t / (1 + t)
        # where x eta >= 0 and 1 / (1 + t) elsewhere, and the curvature is
        # 4 t / (1 + t)^2, with no 1 - sigma cancellation (the hooks' form
        # loses up to 1e-4 of a small curvature in float32).
        e = eta[..., 0, :]
        t = exp_neg(2.0 * jnp.abs(e))
        d = 1.0 + t
        y = 1.0 / d
        y = y + y * (1.0 - d * y)     # one Newton step on the reciprocal
        r = 2.0 * xi * jnp.where(xi * e >= 0, t * y, y)
        kap = 4.0 * t * y * y
        return r[..., None, :], kap[..., None, None, :]

    # ---------------------------------------------------- sampling hooks
    def init_draw(self, key, p: int):
        return jnp.where(jax.random.uniform(key, (p,)) < 0.5, 1.0, -1.0)

    def cond_draw(self, key, eta):
        u = jax.random.uniform(key, eta.shape[:-1])
        return jnp.where(u < jax.nn.sigmoid(2.0 * eta[..., 0]), 1.0, -1.0)

    # ------------------------------------------------------------- model
    def suff_stats(self, graph: Graph, X):
        return I.suff_stats(graph, jnp.asarray(X))

    # ------------------------------------------------------------ oracle
    def exact_moments(self, graph: Graph, theta) -> np.ndarray:
        mu, _ = I.exact_moments(graph, jnp.asarray(theta))
        return np.asarray(mu, dtype=np.float64)

    def exact_sample(self, graph: Graph, theta, n: int, key):
        from ..sampling import exact_sample
        return exact_sample(I.IsingModel(graph, jnp.asarray(theta)), n, key)

    def random_params(self, graph: Graph, key, scale_edge: float = 0.4,
                      scale_node: float = 0.3):
        m = I.random_model(graph, scale_edge, scale_node, key)
        return m.theta
