"""The ``ModelFamily`` contract: one estimator interface per exponential
family (Liu & Ihler 2012 Sec. 2; Liu & Ihler 2014; Mizrahi et al. 2014).

Every family is a pairwise exponential-family model over a :class:`~repro.
core.graphs.Graph` whose per-node conditionals are **channelized GLMs**:
node i's conditional distribution given its neighbors is determined by a
``(C,)`` vector of channel logits

    eta_c(x) = theta_{i,c} + sum_{j in N(i)} theta_{ij,c} * f_c(x_j),

where ``C = family.block_dim`` is the shared per-node / per-edge parameter
block size and ``f`` is the family's :meth:`~ModelFamily.edge_features` map.
Concretely:

* **Ising** — C = 1, f(x) = x, logistic channel likelihood;
* **Gaussian MRF** — C = 1, f(x) = x, unit-variance linear-Gaussian channel
  (the node conditional is weighted least squares, so Newton converges in
  one step);
* **Potts (q states)** — C = q - 1, f_c(x) = 1[x = c + 1], multinomial
  logistic channels with *vector-valued* per-edge parameter blocks.

The flat parameter vector is ordered ``[node blocks (p*C), edge blocks
(m*C)]``, generalizing the seed's ``[singletons, edges]`` layout (C = 1
reproduces it exactly). Families must supply closed-form per-channel score
``dl_deta`` and curvature hooks — that is what lets the degree-bucketed
batched engine (:mod:`repro.core.batched`) solve every family without
autodiff — plus sampler draws and an exact small-p oracle, which is what
the conformance harness (``tests/families/test_conformance.py``) checks
each registered family against.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs import Graph


class ModelFamily:
    """Abstract base for exponential-family model plugins.

    Subclasses are frozen dataclasses holding only hashable configuration
    (name, q, ...), so a family instance can be a static jit argument and a
    dict key in the registry. All array math lives in methods.
    """

    name: str

    # ------------------------------------------------------------ kernels
    @property
    def kernel_kind(self) -> Optional[str]:
        """Epilogue key into the fused CL kernel registry
        (:mod:`repro.kernels.cl.epilogues`), or None for no fused path.

        A family returning a registered kind gets the fused Pallas
        score/Gram pipeline and the fused bucket Newton statistics for
        free; families without one transparently use the closed-form hook
        / autodiff reference paths everywhere.
        """
        return None

    # ------------------------------------------------------------ layout
    @property
    def block_dim(self) -> int:
        """C: size of every per-node and per-edge parameter block."""
        raise NotImplementedError

    def n_params(self, graph: Graph) -> int:
        return (graph.p + graph.m) * self.block_dim

    def node_block(self, graph: Graph, i: int) -> List[int]:
        C = self.block_dim
        return list(range(i * C, (i + 1) * C))

    def edge_block(self, graph: Graph, k: int) -> List[int]:
        C = self.block_dim
        base = graph.p * C
        return list(range(base + k * C, base + (k + 1) * C))

    def beta(self, graph: Graph, i: int,
             include_singleton: bool = True) -> List[int]:
        """Flat indices of the parameters node i estimates, block-ordered:
        singleton block first (when free), then incident-edge blocks in
        ``graph.incident_edges(i)`` order — the generalization of
        ``graph.beta``; identical to it at C = 1."""
        idx = self.node_block(graph, i) if include_singleton else []
        for k in graph.incident_edges(i):
            idx += self.edge_block(graph, k)
        return idx

    def node_params(self, graph: Graph, theta) -> jnp.ndarray:
        """(p, C) node blocks of a flat theta."""
        C = self.block_dim
        return jnp.asarray(theta)[: graph.p * C].reshape(graph.p, C)

    def edge_params(self, graph: Graph, theta) -> jnp.ndarray:
        """(m, C) edge blocks of a flat theta."""
        C = self.block_dim
        return jnp.asarray(theta)[graph.p * C:].reshape(graph.m, C)

    def coupling_tensor(self, graph: Graph, theta) -> jnp.ndarray:
        """Symmetric (p, p, C) dense coupling tensor from the edge blocks."""
        te = self.edge_params(graph, theta)
        rows = np.array([e[0] for e in graph.edges], dtype=np.int32)
        cols = np.array([e[1] for e in graph.edges], dtype=np.int32)
        T = jnp.zeros((graph.p, graph.p, self.block_dim), dtype=te.dtype)
        T = T.at[rows, cols].set(te)
        return T.at[cols, rows].set(te)

    # ----------------------------------------------------- channel hooks
    def edge_features(self, x: jnp.ndarray) -> jnp.ndarray:
        """Per-channel feature of a neighbor's value: (...,) -> (..., C)."""
        raise NotImplementedError

    def loglik_eta(self, eta: jnp.ndarray, xi: jnp.ndarray) -> jnp.ndarray:
        """Per-sample conditional loglik from channel logits.

        eta: (..., C, n); xi: (..., n) node values. Returns (..., n).
        """
        raise NotImplementedError

    def dl_deta(self, eta: jnp.ndarray, xi: jnp.ndarray) -> jnp.ndarray:
        """Closed-form d loglik / d eta: (..., C, n)."""
        raise NotImplementedError

    def curvature(self, eta: jnp.ndarray, xi: jnp.ndarray) -> jnp.ndarray:
        """Closed-form -d^2 loglik / d eta^2, PSD: (..., C, C, n)."""
        raise NotImplementedError

    def sandwich_terms(self, eta: jnp.ndarray, xi: jnp.ndarray):
        """(dl_deta, curvature) at the solution, for the sandwich variance.

        The variance of an ill-conditioned fit amplifies the per-sample
        error of these terms by its condition number, so a family whose
        hooks lose digits (a cancellation, an approximate transcendental)
        overrides this with a more careful form. Default: the hooks.
        """
        return self.dl_deta(eta, xi), self.curvature(eta, xi)

    # --------------------------------------------------- sampling hooks
    def init_draw(self, key: jax.Array, p: int) -> jnp.ndarray:
        """(p,) initial Gibbs state."""
        raise NotImplementedError

    def cond_draw(self, key: jax.Array, eta: jnp.ndarray) -> jnp.ndarray:
        """Draw node values from conditionals: eta (..., C) -> (...)."""
        raise NotImplementedError

    # ------------------------------------------------------------ model
    def suff_stats(self, graph: Graph, X: jnp.ndarray) -> jnp.ndarray:
        """u(x): (n, n_params) in flat block order."""
        raise NotImplementedError

    def cond_logits(self, graph: Graph, theta, X: jnp.ndarray) -> jnp.ndarray:
        """All-node channel logits: (n, p, C)."""
        h = self.node_params(graph, theta)                   # (p, C)
        Tc = self.coupling_tensor(graph, theta)              # (p, p, C)
        F = self.edge_features(jnp.asarray(X))               # (n, p, C)
        return h[None] + jnp.einsum("njc,jic->nic", F, Tc)

    def cond_loglik(self, graph: Graph, theta, X: jnp.ndarray) -> jnp.ndarray:
        """Per-node conditional loglik log p(x_i | x_N(i)): (n, p)."""
        X = jnp.asarray(X)
        eta = self.cond_logits(graph, theta, X)              # (n, p, C)
        ll = self.loglik_eta(jnp.moveaxis(eta, 0, 2), X.T)   # (p, n)
        return ll.T

    def pseudo_loglik(self, graph: Graph, theta, X: jnp.ndarray):
        """Average pseudo-likelihood (Eq. 2 generalized)."""
        return jnp.mean(jnp.sum(self.cond_loglik(graph, theta, X), axis=1))

    def pseudo_score(self, graph: Graph, theta, X: jnp.ndarray) -> np.ndarray:
        """Reference flat gradient of the average pseudo-likelihood."""
        t = jnp.asarray(np.asarray(theta), dtype=jnp.float32)
        g = jax.grad(lambda w: self.pseudo_loglik(graph, w,
                                                  jnp.asarray(X)))(t)
        return np.asarray(g, dtype=np.float64)

    # ------------------------------------------------------------ oracle
    def exact_moments(self, graph: Graph, theta) -> np.ndarray:
        """E[u(x)] under p(x | theta) — small p / closed form only."""
        raise NotImplementedError

    def exact_sample(self, graph: Graph, theta, n: int,
                     key: jax.Array) -> jnp.ndarray:
        """n iid samples from the exact joint (small p / closed form)."""
        raise NotImplementedError

    def random_params(self, graph: Graph, key: jax.Array,
                      scale_edge: float = 0.4,
                      scale_node: float = 0.3) -> jnp.ndarray:
        """A valid random flat theta (families enforce their own
        constraints, e.g. the Gaussian precision staying PD)."""
        raise NotImplementedError

    def sample(self, graph: Graph, theta, n: int, key: jax.Array,
               burnin: int = 200, thin: int = 5,
               n_chains: int = 8) -> jnp.ndarray:
        """Default sampler: family-generic chromatic Gibbs."""
        from ..sampling import gibbs_sample_family
        return gibbs_sample_family(self, graph, theta, n, key,
                                   burnin=burnin, thin=thin,
                                   n_chains=n_chains)


# ---------------------------------------------------------------- generic
def random_rows(family: ModelFamily, key: jax.Array, n: int,
                p: int) -> jnp.ndarray:
    """(n, p) iid rows of *valid* node values via ``family.init_draw``.

    The family-generic cheap sample source benchmarks and property tests
    use when they need well-typed data (spin signs, reals, Potts states)
    without paying for draws from any particular joint model — a fourth
    registered family gets correct rows here automatically instead of
    falling through some name-keyed special case.
    """
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: family.init_draw(k, p))(keys)


# Reference fits shared by every family: plain autodiff Newton on the
# family criteria. Slow but definitionally correct — the conformance
# harness pits the batched engine against these.
def fit_mple_family(family: ModelFamily, graph: Graph, X,
                    free_idx: Optional[Sequence[int]] = None,
                    theta_fixed: Optional[np.ndarray] = None,
                    n_iter: int = 40) -> np.ndarray:
    """Centralized joint MPLE for any family; returns full flat theta."""
    from ..estimators import newton_maximize
    n_params = family.n_params(graph)
    X = jnp.asarray(X)
    if theta_fixed is None:
        theta_fixed = jnp.zeros(n_params, X.dtype)
    theta_fixed = jnp.asarray(theta_fixed, X.dtype)
    if free_idx is None:
        free_idx = np.arange(n_params)
    free_idx = np.asarray(free_idx)

    def fun(w):
        theta = theta_fixed.at[free_idx].set(w)
        return family.pseudo_loglik(graph, theta, X)

    w = newton_maximize(fun, theta_fixed[free_idx], n_iter=n_iter)
    return np.asarray(theta_fixed.at[free_idx].set(w))


def fit_node_oracle(family: ModelFamily, graph: Graph, X, i: int,
                    include_singleton: bool = True,
                    theta_fixed: Optional[np.ndarray] = None,
                    n_iter: int = 40) -> np.ndarray:
    """Node i's local CL fit by autodiff Newton — the per-node oracle.

    Returns the ``family.beta(graph, i, include_singleton)``-ordered local
    parameter vector (block layout identical to the batched engine's).
    """
    from ..estimators import newton_maximize
    C = family.block_dim
    X = jnp.asarray(X)
    if theta_fixed is None:
        theta_fixed = jnp.zeros(family.n_params(graph), X.dtype)
    theta_fixed = jnp.asarray(theta_fixed, X.dtype)

    ks = graph.incident_edges(i)
    others = [graph.edges[k][0] if graph.edges[k][1] == i else graph.edges[k][1]
              for k in ks]
    F = family.edge_features(X[:, others]) if others else \
        jnp.zeros((X.shape[0], 0, C), X.dtype)               # (n, deg, C)
    xi = X[:, i]
    lead = 1 if include_singleton else 0
    d = (lead + len(others)) * C
    offset = theta_fixed[np.asarray(family.node_block(graph, i))]

    def fun(w):
        Wb = w.reshape(lead + len(others), C)
        We = Wb[lead:]                                       # (deg, C)
        eta = jnp.einsum("njc,jc->nc", F, We)                # (n, C)
        eta = eta + (Wb[0][None, :] if include_singleton else offset[None, :])
        return jnp.mean(family.loglik_eta(eta.T, xi))

    w = newton_maximize(fun, jnp.zeros(d, X.dtype), n_iter=n_iter)
    return np.asarray(w)
