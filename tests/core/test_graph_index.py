"""The graph's incidence index against a plain edge scan.

``Graph`` builds a CSR incidence index once; every per-node query
(``incident_edges``, ``neighbors``, ``degree``, ``beta``, ``family.beta``)
and the derived engine structures (degree buckets, parameter owners) read
it. Each must return exactly what a scan of the edge list returns, in the
same order, and the index must not change what a graph is equal to,
hashes to or serialises as.
"""
import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import jax
import numpy as np
import pytest

import repro.api as A
import repro.core as C
from repro.api.session import EstimationSession
from repro.core import asymptotics, batched
from repro.core.batched import DegreeBucket, _pad_degree, degree_buckets
from repro.core.families import GaussianMRF, IsingFamily, PottsFamily
from repro.core.graphs import Graph

from bench import reference

FAMILIES = (IsingFamily(), GaussianMRF(), PottsFamily(3))

GRAPHS = {
    "chain": lambda: C.chain_graph(9),
    "star": lambda: C.star_graph(8),
    "grid": lambda: C.grid_graph(4, 5),
    "complete": lambda: C.complete_graph(6),
    "scale_free": lambda: C.scale_free_graph(40, m=2, seed=3),
    "euclidean": lambda: C.euclidean_graph(30, radius=0.3, seed=1),
    "isolated_nodes": lambda: Graph(7, ((1, 4), (1, 6), (2, 4))),
    "edgeless": lambda: Graph(5, ()),
}


# ------------------------------------------------------------ plain scans
def scan_incident_edges(g: Graph, i: int) -> List[int]:
    return [k for k, (a, b) in enumerate(g.edges) if i in (a, b)]


def scan_neighbors(g: Graph, i: int) -> List[int]:
    return sorted(b if a == i else a for (a, b) in g.edges if i in (a, b))


def scan_beta(g: Graph, i: int, include_singleton: bool) -> List[int]:
    return ([i] if include_singleton else []) + [
        g.p + k for k in scan_incident_edges(g, i)]


def scan_family_beta(family, g: Graph, i: int,
                     include_singleton: bool) -> List[int]:
    C_ = family.block_dim
    idx = list(range(i * C_, (i + 1) * C_)) if include_singleton else []
    for k in scan_incident_edges(g, i):
        idx += list(range(g.p * C_ + k * C_, g.p * C_ + (k + 1) * C_))
    return idx


def scan_degree_buckets(g: Graph) -> tuple:
    by_pad: Dict[int, List[int]] = {}
    for i in range(g.p):
        by_pad.setdefault(_pad_degree(len(scan_incident_edges(g, i))),
                          []).append(i)
    out = []
    for pad in sorted(by_pad):
        nodes = np.asarray(by_pad[pad], dtype=np.int32)
        nbrs = np.zeros((len(nodes), pad), dtype=np.int32)
        mask = np.zeros((len(nodes), pad), dtype=np.float32)
        for row, i in enumerate(nodes):
            others = [g.edges[k][0] if g.edges[k][1] == i else g.edges[k][1]
                      for k in scan_incident_edges(g, int(i))]
            nbrs[row, :len(others)] = others
            mask[row, :len(others)] = 1.0
        out.append(DegreeBucket(deg_pad=pad, nodes=nodes, nbrs=nbrs,
                                mask=mask))
    return tuple(out)


def scan_param_owners(g: Graph, include_singleton: bool, family=None):
    owners: Dict[int, list] = {}
    for i in range(g.p):
        beta = (scan_beta(g, i, include_singleton) if family is None
                else scan_family_beta(family, g, i, include_singleton))
        for pos, a in enumerate(beta):
            owners.setdefault(a, []).append((i, pos))
    return owners


def _same_buckets(got, want) -> None:
    assert len(got) == len(want)
    for b, w in zip(got, want):
        assert type(b.deg_pad) is int and b.deg_pad == w.deg_pad
        for field in ("nodes", "nbrs", "mask"):
            x, y = getattr(b, field), getattr(w, field)
            assert x.dtype == y.dtype and np.array_equal(x, y), field


# ------------------------------------------------------------ the index
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_index_matches_a_plain_edge_scan(name):
    g = GRAPHS[name]()
    for i in range(g.p):
        assert g.incident_edges(i) == scan_incident_edges(g, i)
        assert type(g.incident_edges(i)) is list
        assert g.neighbors(i) == scan_neighbors(g, i)
        assert g.degree(i) == len(scan_neighbors(g, i))
        for inc in (True, False):
            assert g.beta(i, inc) == scan_beta(g, i, inc)
            for fam in FAMILIES:
                assert fam.beta(g, i, inc) == scan_family_beta(fam, g, i,
                                                               inc)
    ptr, edge, other = g.incidence()
    assert not (ptr.flags.writeable or edge.flags.writeable
                or other.flags.writeable)
    assert ptr.tolist() == np.cumsum(
        [0] + [len(scan_incident_edges(g, i)) for i in range(g.p)]).tolist()
    for i in range(g.p):
        assert sorted(other[ptr[i]:ptr[i + 1]].tolist()) == \
            scan_neighbors(g, i)
    _same_buckets(degree_buckets(g), scan_degree_buckets(g))
    for inc in (True, False):
        assert asymptotics.param_owners(g, inc) == scan_param_owners(g, inc)
        for fam in FAMILIES:
            assert asymptotics.param_owners(g, inc, fam) == \
                scan_param_owners(g, inc, fam)

    # the index is not part of what a graph is
    twin = Graph(g.p, tuple(g.edges))
    assert twin == g and hash(twin) == hash(g) and repr(twin) == repr(g)
    assert [f.name for f in dataclasses.fields(g)] == ["p", "edges"]
    assert dataclasses.asdict(g) == {"p": g.p, "edges": g.edges}
    assert repr(g) == f"Graph(p={g.p}, edges={g.edges!r})"
    plan = A.Plan(graph=g, combiners=("diagonal",))
    back = A.Plan.from_dict(plan.to_dict())
    assert back == plan and hash(back) == hash(plan)
    assert back.to_dict() == plan.to_dict()


# ------------------------------------------------------------ a whole fit
def _scan_path(monkeypatch):
    """Route every per-node graph query and the engine's derived
    structures through the plain scans, as before the index."""
    monkeypatch.setattr(Graph, "incident_edges", scan_incident_edges)
    monkeypatch.setattr(Graph, "neighbors", scan_neighbors)
    monkeypatch.setattr(Graph, "degree",
                        lambda g, i: len(scan_neighbors(g, i)))
    monkeypatch.setattr(batched, "_degree_buckets_cached",
                        scan_degree_buckets)
    monkeypatch.setattr(asymptotics, "_param_owners_cached",
                        lambda g, inc, fam: scan_param_owners(g, inc, fam))


def test_lattice_fit_is_bit_identical_to_the_scan_path_and_the_reference(
        monkeypatch):
    g = C.grid_graph(16, 16)
    rng = np.random.RandomState(5)
    theta = np.concatenate([rng.normal(0.0, 0.5, g.p),
                            rng.normal(0.0, 0.5, g.m)]).astype(np.float32)
    X = C.gibbs_sample(C.IsingModel(g, jax.numpy.asarray(theta)), 1024,
                       jax.random.PRNGKey(5))
    plan = A.Plan(graph=g, combiners=("diagonal",))

    def fit():
        res = EstimationSession(plan).fit(X)
        return ([(f.i, f.beta, np.asarray(f.theta)) for f in res.fits],
                np.asarray(res.combined["diagonal"]))

    fits, combined = fit()
    with monkeypatch.context() as m:
        _scan_path(m)
        scan_fits, scan_combined = fit()
    assert len(fits) == len(scan_fits) == g.p
    for (i, beta, th), (i0, beta0, th0) in zip(fits, scan_fits):
        assert i == i0 and beta == beta0
        assert th.dtype == th0.dtype and th.tobytes() == th0.tobytes()
    assert combined.tobytes() == scan_combined.tobytes()

    # and both agree with the float64 reference within the limits of the
    # benchmark's lattice cell
    limits = json.loads((Path(reference.__file__).parent / "checks"
                         / "lattice64.fit.json").read_text())["limits"]
    Xh = np.asarray(X, np.float64)
    ref = reference.local_fits("ising", Xh, g.p, g.edges)
    local = reference.local_gaps({i: th for i, _, th in fits}, ref)
    assert local.max() <= limits["local_theta_gap"]
    want = reference.combine("diagonal", ref, g.p, g.edges)
    gaps = reference.combined_gaps(combined, want)
    assert gaps.max() <= limits["combined_theta_gap"]
