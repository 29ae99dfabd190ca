"""Benchmark-owned inputs: graphs, true parameters and samples.

Everything a cell feeds the program is made here from the cell's
configuration and ``--seed``, so a change to the program's own graph
constructors or samplers cannot move the yardstick. Graphs are fixed by the
configuration (``graph_seed``): every seed then drives the same compiled
shapes. Parameters and samples come from ``--seed`` and are drawn on the
device. Where the configuration names a ``param_seed``, the true
parameters are one draw from that seed, put in a sign gauge drawn from
``--seed`` (x_i -> s_i x_i, s_i = +-1: theta_i -> s_i theta_i and
theta_ij -> s_i s_j theta_ij). Both families are exactly invariant under
it, so every seed poses a problem of one difficulty (the same Newton
iterations, the same conditioning) on samples of its own. Samples are:

* Ising samples by chromatic Gibbs over independent chains (one chain per
  row, ``gibbs_sweeps`` full sweeps each), so the rows are independent;
* Gaussian samples exactly, from the precision matrix ``I - T``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class BenchGraph:
    """p nodes and sorted undirected edges (i < j)."""
    p: int
    edges: Tuple[Tuple[int, int], ...]

    @property
    def m(self) -> int:
        return len(self.edges)

    def neighbors(self) -> List[np.ndarray]:
        """Ascending neighbour indices of every node: the order of its
        incident edges in the sorted edge list."""
        nb: List[list] = [[] for _ in range(self.p)]
        for i, j in self.edges:
            nb[i].append(j)
            nb[j].append(i)
        return [np.asarray(sorted(v), dtype=np.int64) for v in nb]

    def degrees(self) -> np.ndarray:
        return np.asarray([len(v) for v in self.neighbors()], dtype=np.int64)

    def coloring(self) -> np.ndarray:
        """Greedy colouring, largest degree first: same-colour nodes are
        never adjacent, so a Gibbs sweep updates a colour class at once."""
        nb = self.neighbors()
        colors = np.full(self.p, -1, dtype=np.int64)
        for i in sorted(range(self.p), key=lambda v: (-len(nb[v]), v)):
            used = {int(colors[j]) for j in nb[i] if colors[j] >= 0}
            c = 0
            while c in used:
                c += 1
            colors[i] = c
        return colors


def barabasi_albert(p: int, m: int, seed: int) -> BenchGraph:
    """Preferential attachment (Barabasi & Albert 1999): a seed clique of
    m + 1 nodes, then each new node links to m distinct earlier nodes
    drawn with probability proportional to degree."""
    rng = np.random.RandomState(seed)
    edges = set()
    deg = np.zeros(p, dtype=np.int64)
    for i in range(m + 1):
        for j in range(i + 1, m + 1):
            edges.add((i, j))
            deg[i] += 1
            deg[j] += 1
    for new in range(m + 1, p):
        targets = set()
        while len(targets) < m:
            targets.add(int(rng.choice(new, p=deg[:new] / deg[:new].sum())))
        for t in targets:
            edges.add((t, new))
            deg[t] += 1
            deg[new] += 1
    return BenchGraph(p, tuple(sorted(edges)))


def grid(rows: int, cols: int) -> BenchGraph:
    """Four-neighbour lattice, node r * cols + c."""
    edges = []
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            if c + 1 < cols:
                edges.append((i, i + 1))
            if r + 1 < rows:
                edges.append((i, i + cols))
    return BenchGraph(rows * cols, tuple(sorted(edges)))


def build_graph(cfg: dict) -> BenchGraph:
    kind = cfg["graph"]
    if kind == "barabasi_albert":
        return barabasi_albert(int(cfg["p"]), int(cfg["ba_m"]),
                               int(cfg["graph_seed"]))
    if kind == "grid":
        return grid(int(cfg["rows"]), int(cfg["cols"]))
    raise ValueError(f"unknown graph kind {kind!r}")


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds may exceed
    32 bits) and a stream index that separates independent draws."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, dtype=np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) >> 1),
                              int(words[1]) >> 1)


def _neighbor_table(g: BenchGraph):
    """(p, dmax) neighbour indices padded with the node itself, and the
    (p, dmax) edge index of each entry (m for padding)."""
    nb = g.neighbors()
    dmax = max(1, max(len(v) for v in nb))
    idx = np.tile(np.arange(g.p)[:, None], (1, dmax))
    eid = np.full((g.p, dmax), g.m, dtype=np.int64)
    where = {e: k for k, e in enumerate(g.edges)}
    for i, v in enumerate(nb):
        idx[i, :len(v)] = v
        eid[i, :len(v)] = [where[(min(i, j), max(i, j))] for j in v]
    return idx, eid


@functools.partial(jax.jit, static_argnames=("p", "m", "node_scale",
                                             "edge_scale"))
def _normal_params(key, *, p, m, node_scale, edge_scale):
    k1, k2 = jax.random.split(key)
    return jnp.concatenate([node_scale * jax.random.normal(k1, (p,)),
                            edge_scale * jax.random.normal(k2, (m,))])


@functools.partial(jax.jit, static_argnames=("n", "sweeps"))
def _ising_gibbs(key, h, coupling, nbr, colors, *, n, sweeps):
    """(n, p) float32 +-1 rows: n independent chromatic-Gibbs chains.

    coupling: (p, dmax) coupling of each neighbour slot (0 on padding);
    nbr: (p, dmax) neighbour index; colors: (n_colors, p) 0/1 masks."""
    p = h.shape[0]
    k0, kr = jax.random.split(key)
    x = jnp.where(jax.random.uniform(k0, (n, p)) < 0.5, 1.0, -1.0)

    def sweep(x, k):
        for c in range(colors.shape[0]):
            eta = h + jnp.sum(coupling * x[:, nbr], axis=-1)
            u = jax.random.uniform(jax.random.fold_in(k, c), (n, p))
            new = jnp.where(u < jax.nn.sigmoid(2.0 * eta), 1.0, -1.0)
            x = jnp.where(colors[c] > 0, new, x)
        return x, None

    x, _ = jax.lax.scan(sweep, x, jax.random.split(kr, sweeps))
    return x


def ising_params(g: BenchGraph, key, cfg: dict) -> jax.Array:
    """theta* = [theta_i ~ N(0, node_scale^2), theta_ij ~ N(0,
    edge_scale^2)], the paper's Sec. 5 draw."""
    return _normal_params(key, p=g.p, m=g.m,
                          node_scale=float(cfg["node_scale"]),
                          edge_scale=float(cfg["edge_scale"]))


def ising_samples(g: BenchGraph, theta, key, n: int, sweeps: int):
    idx, eid = _neighbor_table(g)
    te = jnp.concatenate([theta[g.p:], jnp.zeros((1,), theta.dtype)])
    colors = g.coloring()
    masks = np.stack([(colors == c) for c in range(colors.max() + 1)])
    return _ising_gibbs(key, theta[:g.p], te[jnp.asarray(eid)],
                        jnp.asarray(idx), jnp.asarray(masks, jnp.float32),
                        n=n, sweeps=sweeps)


@functools.partial(jax.jit, static_argnames=("p",))
def _gaussian_params(key, rows, cols, *, p, node_scale, edge_scale):
    k1, k2 = jax.random.split(key)
    h = node_scale * jax.random.normal(k1, (p,))
    te = edge_scale * jax.random.normal(k2, rows.shape)
    # keep I - T strictly diagonally dominant, hence positive definite
    load = (jnp.zeros(p).at[rows].add(jnp.abs(te))
            .at[cols].add(jnp.abs(te)))
    te = te * jnp.minimum(1.0, 0.9 / jnp.maximum(jnp.max(load), 1e-30))
    return jnp.concatenate([h, te])


@functools.partial(jax.jit, static_argnames=("n",))
def _gaussian_draw(key, theta, rows, cols, *, n):
    p = theta.shape[0] - rows.shape[0]
    T = jnp.zeros((p, p)).at[rows, cols].set(theta[p:]) \
        .at[cols, rows].set(theta[p:])
    J = jnp.eye(p) - T
    L = jnp.linalg.cholesky(J)                       # J = L L^T
    mu = jax.scipy.linalg.cho_solve((L, True), theta[:p])
    z = jax.random.normal(key, (p, n))
    # x = mu + L^{-T} z has covariance (L L^T)^{-1} = J^{-1}
    x = jax.scipy.linalg.solve_triangular(L.T, z, lower=False)
    return (mu[:, None] + x).T


def _edge_arrays(g: BenchGraph):
    e = np.asarray(g.edges, dtype=np.int32).reshape(-1, 2)
    return jnp.asarray(e[:, 0]), jnp.asarray(e[:, 1])


def gaussian_params(g: BenchGraph, key, cfg: dict) -> jax.Array:
    rows, cols = _edge_arrays(g)
    return _gaussian_params(key, rows, cols, p=g.p,
                            node_scale=float(cfg["gauss_node_scale"]),
                            edge_scale=float(cfg["gauss_edge_scale"]))


def gaussian_samples(g: BenchGraph, theta, key, n: int):
    rows, cols = _edge_arrays(g)
    with jax.default_matmul_precision("highest"):
        return _gaussian_draw(key, theta, rows, cols, n=n)


def family_params(family: str, g: BenchGraph, key, cfg: dict):
    if family == "ising":
        return ising_params(g, key, cfg)
    if family == "gaussian":
        return gaussian_params(g, key, cfg)
    raise ValueError(f"no generator for family {family!r}")


def family_samples(family: str, g: BenchGraph, theta, key, n: int,
                   cfg: dict):
    if family == "ising":
        return ising_samples(g, theta, key, n, int(cfg["gibbs_sweeps"]))
    if family == "gaussian":
        return gaussian_samples(g, theta, key, n)
    raise ValueError(f"no sampler for family {family!r}")


@jax.jit
def _gauge(theta, signs, rows, cols):
    p = signs.shape[0]
    return jnp.concatenate([theta[:p] * signs,
                            theta[p:] * signs[rows] * signs[cols]])


def true_params(family: str, g: BenchGraph, seed: int, cfg: dict,
                stream: int = 0):
    """theta*: a draw from ``--seed``, or, where the configuration names
    a ``param_seed``, the draw from that seed in a sign gauge drawn from
    ``--seed``."""
    key = seed_key(seed, 2 * stream)
    if cfg.get("param_seed") is None:
        return family_params(family, g, key, cfg)
    theta = family_params(family, g,
                          seed_key(int(cfg["param_seed"]), 2 * stream), cfg)
    signs = jax.random.rademacher(key, (g.p,), dtype=jnp.float32)
    return _gauge(theta, signs, *_edge_arrays(g))


def sample_sets(family: str, g: BenchGraph, seed: int, cfg: dict,
                n_sets: int, n: int, stream: int = 0):
    """(theta*, [n_sets device arrays of (n, p) float32 rows]): one true
    parameter vector and ``n_sets`` independent sample sets, all a pure
    function of (configuration, family, seed, stream)."""
    theta = true_params(family, g, seed, cfg, stream)
    base = seed_key(seed, 2 * stream + 1)
    sets = [family_samples(family, g, theta, jax.random.fold_in(base, s),
                           n, cfg) for s in range(n_sets)]
    return theta, sets
