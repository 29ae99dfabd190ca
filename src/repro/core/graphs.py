"""Graph structures for pairwise graphical models / sensor networks.

A ``Graph`` is an immutable container of ``p`` nodes and undirected edges
``(i, j)`` with ``i < j``. The flat parameter vector for an Ising model on a
graph is ordered ``[theta_1..theta_p, theta_e1..theta_em]`` (singletons first,
then edges in ``graph.edges`` order); see :mod:`repro.core.ising`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import numpy as np

Edge = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Graph:
    p: int
    edges: Tuple[Edge, ...]

    def __post_init__(self):
        seen = set()
        for (i, j) in self.edges:
            if not (0 <= i < j < self.p):
                raise ValueError(f"bad edge ({i},{j}) for p={self.p}")
            if (i, j) in seen:
                raise ValueError(f"duplicate edge ({i},{j})")
            seen.add((i, j))
        self._build_incidence()

    def _build_incidence(self) -> None:
        """CSR incidence index, built once in O(m log m): node i's incident
        edge ids (ascending, i.e. ``edges`` order) are ``_inc_edge[
        _inc_ptr[i]:_inc_ptr[i + 1]]`` and ``_inc_other`` holds the other
        endpoint of each. Plain attributes, not dataclass fields, so
        equality, hashing and ``repr`` still see only ``(p, edges)``."""
        ends = np.asarray(self.edges, dtype=np.int64).reshape(-1, 2)
        ids = np.arange(len(ends), dtype=np.int64)
        node = np.concatenate([ends[:, 0], ends[:, 1]])
        edge = np.concatenate([ids, ids])
        other = np.concatenate([ends[:, 1], ends[:, 0]])
        order = np.lexsort((edge, node))
        ptr = np.zeros(self.p + 1, dtype=np.int64)
        np.cumsum(np.bincount(node, minlength=self.p), out=ptr[1:])
        for name, arr in (("_inc_ptr", ptr), ("_inc_edge", edge[order]),
                          ("_inc_other", other[order])):
            arr.flags.writeable = False
            object.__setattr__(self, name, arr)

    def incidence(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The incidence index ``(ptr, edge, other)`` as read-only arrays:
        node i's incident edge ids, in ``edges`` order, are
        ``edge[ptr[i]:ptr[i + 1]]`` and ``other`` holds the other endpoint
        of each."""
        return self._inc_ptr, self._inc_edge, self._inc_other

    @property
    def m(self) -> int:
        return len(self.edges)

    @property
    def n_params(self) -> int:
        """Size of flat parameter vector: singletons + edges."""
        return self.p + self.m

    @property
    def adjacency(self) -> np.ndarray:
        A = np.zeros((self.p, self.p), dtype=np.float32)
        for (i, j) in self.edges:
            A[i, j] = A[j, i] = 1.0
        return A

    @property
    def edge_index(self) -> Dict[Edge, int]:
        """Edge -> position in the edge block of the flat parameter vector."""
        return {e: k for k, e in enumerate(self.edges)}

    def neighbors(self, i: int) -> List[int]:
        lo, hi = self._inc_ptr[i], self._inc_ptr[i + 1]
        return sorted(self._inc_other[lo:hi].tolist())

    def degree(self, i: int) -> int:
        return int(self._inc_ptr[i + 1] - self._inc_ptr[i])

    def incident_edges(self, i: int) -> List[int]:
        """Edge-block indices of edges touching node i (in edges order)."""
        return self._inc_edge[self._inc_ptr[i]:self._inc_ptr[i + 1]].tolist()

    def beta(self, i: int, include_singleton: bool = True) -> List[int]:
        """Flat-parameter indices in beta_i = {alpha : i in alpha}.

        With ``include_singleton=False`` (the paper's known-singleton small
        experiments) only incident-edge parameters are returned.
        """
        idx = [i] if include_singleton else []
        idx += [self.p + k for k in self.incident_edges(i)]
        return idx

    def greedy_coloring(self) -> np.ndarray:
        """Proper vertex coloring by greedy largest-degree-first assignment.

        Returns a (p,) int array of color ids in [0, n_colors). Nodes of the
        same color are mutually non-adjacent, so a Gibbs sweep may update a
        whole color class in parallel (chromatic Gibbs). Cached per graph
        (graphs are frozen); callers in sampler replicate loops hit the
        cache instead of redoing the Python sweep.
        """
        return _greedy_coloring_cached(self).copy()


@functools.lru_cache(maxsize=64)
def _greedy_coloring_cached(graph: Graph) -> np.ndarray:
    nbrs = {i: set() for i in range(graph.p)}
    for (a, b) in graph.edges:
        nbrs[a].add(b)
        nbrs[b].add(a)
    colors = np.full(graph.p, -1, dtype=np.int64)
    order = sorted(range(graph.p), key=lambda i: -len(nbrs[i]))
    for i in order:
        used = {colors[j] for j in nbrs[i] if colors[j] >= 0}
        c = 0
        while c in used:
            c += 1
        colors[i] = c
    return colors


# ---------------------------------------------------------------- factories
def chain_graph(p: int) -> Graph:
    return Graph(p, tuple((i, i + 1) for i in range(p - 1)))


def star_graph(p: int) -> Graph:
    """Node 0 is the hub; nodes 1..p-1 are leaves."""
    return Graph(p, tuple((0, i) for i in range(1, p)))


def grid_graph(rows: int, cols: int) -> Graph:
    edges = []
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            if c + 1 < cols:
                edges.append((i, i + 1))
            if r + 1 < rows:
                edges.append((i, i + cols))
    return Graph(rows * cols, tuple(sorted(set(edges))))


def complete_graph(p: int) -> Graph:
    return Graph(p, tuple((i, j) for i in range(p) for j in range(i + 1, p)))


def scale_free_graph(p: int, m: int = 1, seed: int = 0) -> Graph:
    """Barabasi-Albert preferential attachment (Barabasi & Albert, 1999)."""
    rng = np.random.RandomState(seed)
    edges = set()
    degrees = np.zeros(p, dtype=np.int64)
    # seed clique of m+1 nodes
    for i in range(m + 1):
        for j in range(i + 1, m + 1):
            edges.add((i, j))
            degrees[i] += 1
            degrees[j] += 1
    for new in range(m + 1, p):
        targets = set()
        while len(targets) < m:
            probs = degrees[:new] / degrees[:new].sum()
            t = int(rng.choice(new, p=probs))
            targets.add(t)
        for t in targets:
            edges.add((min(t, new), max(t, new)))
            degrees[t] += 1
            degrees[new] += 1
    return Graph(p, tuple(sorted(edges)))


def euclidean_graph(p: int, radius: float = 0.15, seed: int = 0) -> Graph:
    """Random geometric graph on [0,1]^2 connecting nodes within ``radius``."""
    rng = np.random.RandomState(seed)
    pts = rng.rand(p, 2)
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    edges = tuple(
        (i, j) for i in range(p) for j in range(i + 1, p)
        if d2[i, j] <= radius ** 2
    )
    return Graph(p, edges)
