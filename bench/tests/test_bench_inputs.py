"""Benchmark inputs are a pure function of the configuration and seed."""
import numpy as np
import pytest

from bench import inputs

FLEET = {"graph": "barabasi_albert", "p": 30, "ba_m": 1, "graph_seed": 0,
         "node_scale": 0.5, "edge_scale": 0.5, "gauss_node_scale": 0.3,
         "gauss_edge_scale": 0.4, "gibbs_sweeps": 20}
BIG = 2**33 + 12345


def _draw(family, seed, stream=0):
    g = inputs.build_graph(FLEET)
    theta, sets = inputs.sample_sets(family, g, seed, FLEET, 2, 64, stream)
    return np.asarray(theta), [np.asarray(x) for x in sets]


@pytest.mark.parametrize("family", ["ising", "gaussian"])
def test_same_seed_same_inputs(family):
    t1, s1 = _draw(family, BIG)
    t2, s2 = _draw(family, BIG)
    np.testing.assert_array_equal(t1, t2)
    for a, b in zip(s1, s2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("family", ["ising", "gaussian"])
def test_other_seed_or_stream_other_inputs(family):
    t1, s1 = _draw(family, BIG)
    t2, s2 = _draw(family, BIG + 1)
    t3, _ = _draw(family, BIG, stream=1)
    assert not np.array_equal(t1, t2) and not np.array_equal(t1, t3)
    assert not np.array_equal(s1[0], s2[0])
    assert not np.array_equal(s1[0], s1[1])      # sets differ


def test_graphs_are_fixed_by_the_configuration():
    a, b = inputs.build_graph(FLEET), inputs.build_graph(FLEET)
    assert a == b and a.m == a.p - 1              # BA with m = 1: a tree
    lat = inputs.build_graph({"graph": "grid", "rows": 4, "cols": 5})
    assert lat.p == 20 and lat.m == 4 * 4 + 3 * 5
    assert all(i < j for i, j in lat.edges)
    assert list(lat.edges) == sorted(lat.edges)


def test_ising_rows_are_spins_and_colouring_is_proper():
    g = inputs.build_graph(FLEET)
    _, sets = _draw("ising", 3)
    assert set(np.unique(sets[0])) <= {-1.0, 1.0}
    col = g.coloring()
    assert all(col[i] != col[j] for i, j in g.edges)


def test_gaussian_rows_have_the_model_covariance():
    g = inputs.grid(2, 2)
    theta = inputs.gaussian_params(g, inputs.seed_key(5), FLEET)
    X = np.asarray(inputs.gaussian_samples(g, theta, inputs.seed_key(6),
                                           200_000), np.float64)
    th = np.asarray(theta, np.float64)
    J = np.eye(4)
    for k, (i, j) in enumerate(g.edges):
        J[i, j] = J[j, i] = -th[4 + k]
    np.testing.assert_allclose(np.cov(X.T), np.linalg.inv(J), atol=0.02)
    np.testing.assert_allclose(X.mean(0), np.linalg.solve(J, th[:4]),
                               atol=0.02)


def test_seed_key_takes_large_seeds():
    k1, k2 = inputs.seed_key(2**40), inputs.seed_key(2**40 + 1)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


@pytest.mark.parametrize("family", ["ising", "gaussian"])
def test_param_seed_gives_every_seed_one_model_in_its_own_gauge(family):
    cfg = dict(FLEET, param_seed=4)
    g = inputs.build_graph(cfg)
    a = np.asarray(inputs.true_params(family, g, BIG, cfg))
    b = np.asarray(inputs.true_params(family, g, BIG + 1, cfg))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.abs(a), np.abs(b))
    # one sign per node explains every change: s_i on theta_i, s_i s_j
    # on theta_ij
    s = np.sign(a[:g.p]) * np.sign(b[:g.p])
    e = np.asarray(g.edges)
    np.testing.assert_array_equal(np.sign(a[g.p:]) * s[e[:, 0]] * s[e[:, 1]],
                                  np.sign(b[g.p:]))
    # the magnitudes are the param_seed's draw; without it, the seed's own
    ref = np.asarray(inputs.family_params(family, g, inputs.seed_key(4), cfg))
    np.testing.assert_array_equal(np.abs(a), np.abs(ref))
    own = np.asarray(inputs.true_params(family, g, BIG, FLEET))
    assert not np.array_equal(np.abs(own), np.abs(a))
