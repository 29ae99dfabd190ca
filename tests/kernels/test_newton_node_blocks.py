"""Node-blocked bucket Newton kernel: equivalence, the tile rule, telemetry.

One grid step of :func:`repro.kernels.cl.newton.bucket_newton_stats`
covers a block of ``kb`` bucket nodes x ``bm`` samples. Nodes are padded
up to a multiple of the block and samples up to a multiple of the tile;
both paddings must be invisible in (g, K), in the VPU form (narrow
buckets) and in the MXU form (wide ones). The tile rule picks (kb, bm)
from the bucket's shape under a VMEM budget.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cl import autotune as at
from repro.kernels.cl import newton
from repro.kernels.cl.autotune import TileConfig
from repro.kernels.cl.newton import (bucket_newton_prepare,
                                     bucket_newton_stats,
                                     bucket_newton_stats_ref)
from repro.kernels.cl.ops import bucket_newton_op
from repro.telemetry import Recorder, TelemetrySpec

C_OF = {"ising": 1, "gaussian": 1, "potts": 2}

#: (k, d, kb, bm): bucket sizes that are not multiples of the node block,
#: n = 45 samples that are not a multiple of the sample tile; d * C <= 6
#: takes the VPU form, the d = 9 rows the MXU form
BLOCKS = [
    (1, 3, 8, 16),
    (7, 2, 8, 128),
    (7, 5, 16, 32),
    (65, 3, 8, 16),
    (65, 2, 16, 128),
    (65, 9, 16, 32),
    (65, 5, 32, 8),
]


def _case(kind, k, d, n, weighted, seed=0):
    C = C_OF[kind]
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


@pytest.mark.parametrize("k, d, kb, bm", BLOCKS)
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("kind", sorted(C_OF))
def test_node_blocks_match_ref(kind, weighted, k, d, kb, bm):
    """Interpret-mode kernel at an explicit (kb, bm) == the jnp reference,
    node and sample padding included."""
    Zb, base, xi, W, sw = _case(kind, k, d, 45, weighted)
    g0, K0 = bucket_newton_stats_ref(kind, Zb, base, xi, W, sw)
    g1, K1 = bucket_newton_stats(kind, Zb, base, xi, W, sw, interpret=True,
                                 tiles=TileConfig(bm=bm, kb=kb))
    assert g1.shape == g0.shape and K1.shape == K0.shape
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=2e-5)
    np.testing.assert_allclose(np.asarray(K1), np.asarray(K0), atol=2e-5)


# ------------------------------------------------------------ the tile rule
#: every bucket of the benchmark's two cells: (k, d, n). fleet_sf: the
#: Barabasi-Albert tree's degree buckets at n = 4000; lattice64: its one
#: 4096-row bucket and the 64-row finish of its slowest rows, n = 8192
CELL_BUCKETS = [(68, 2, 4000), (23, 5, 4000), (8, 17, 4000), (1, 65, 4000),
                (4096, 5, 8192), (64, 5, 8192)]


def test_tile_rule_is_deterministic():
    for k, d, n in CELL_BUCKETS:
        for weighted in (False, True):
            first = newton.newton_tile_rule(k, d, 1, n, weighted)
            assert newton.newton_tile_rule(k, d, 1, n, weighted) == first


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("k, d, n", CELL_BUCKETS)
def test_tile_rule_fits_the_vmem_budget(k, d, n, weighted):
    kb, bm = newton.newton_tile_rule(k, d, 1, n, weighted)
    assert bm % 128 == 0 and kb % 8 == 0
    assert newton.newton_vmem_bytes(kb, bm, d=d, C=1,
                                    weighted=weighted) <= newton.VMEM_BUDGET
    # the TPU heuristic the dispatch resolves is that rule
    cfg = at._default_tiles("newton", n=n, p=d, C=1, backend="tpu", k=k,
                            weighted=weighted)
    assert (cfg.kb, cfg.bm) == (kb, bm)
    at.validate_tile_config(cfg, "newton", compiled=True)


def test_lattice_bucket_takes_64x_fewer_grid_steps():
    """One node x 128 samples per step made 4096 x 64 = 262,144 steps per
    Newton iteration on the lattice's bucket."""
    kb, bm = newton.newton_tile_rule(4096, 5, 1, 8192, False)
    steps = newton.newton_grid_steps(4096, 8192, kb, bm)
    assert steps * 64 <= 4096 * (8192 // 128)


def test_node_block_is_cut_to_the_bucket():
    assert newton.node_block(5, 16) == (5, 5)        # small: whole bucket
    assert newton.node_block(64, 128) == (64, 64)
    assert newton.node_block(65, 16) == (16, 80)
    assert newton.node_block(68, 128) == (72, 72)


def test_validate_rejects_a_node_block_off_the_sublanes():
    with pytest.raises(ValueError, match="8-multiple kb"):
        at.validate_tile_config(TileConfig(bm=128, kb=12), "newton",
                                compiled=True)
    with pytest.raises(ValueError, match="kb"):
        at.validate_tile_config(TileConfig(bm=128, kb=0), "newton")
    ok = TileConfig(bm=256, kb=16)
    assert at.validate_tile_config(ok, "newton", compiled=True) is ok
    # interpret mode takes any positive block
    at.validate_tile_config(TileConfig(bm=16, kb=3), "newton")


def test_cache_entry_without_node_block_falls_back(tmp_path, monkeypatch):
    """A tune-cache entry written before ``kb`` existed loads with
    ``kb=None``, and the kernel then takes the rule's block for its bm."""
    monkeypatch.delenv("REPRO_CL_TUNE_CACHE", raising=False)
    at.clear_cache()
    key = at.tile_key("newton", n=45, p=5, C=1, backend="tpu", k=20)
    path = tmp_path / "old.json"
    path.write_text(json.dumps({"version": 1, "entries": {
        key: {"bm": 1024, "bn": 128, "bk": 128}}}))
    try:
        assert at.load_cache(str(path)) == 1
        cfg = at.get_tiles("newton", n=45, p=5, C=1, backend="tpu", k=20)
        assert cfg == TileConfig(bm=1024, kb=None)
        Zb, base, xi, W, _ = _case("ising", 20, 5, 45, False)
        stats = bucket_newton_prepare("ising", Zb, base, xi, interpret=True,
                                      tiles=cfg)
        kb, _ = newton.newton_tile_rule(20, 5, 1, 45, False, bm=1024)
        assert stats.bm == 1024
        assert stats.node_block == newton.node_block(20, kb)[0]
        g0, K0 = bucket_newton_stats_ref("ising", Zb, base, xi, W)
        g1, K1 = stats(W)
        np.testing.assert_allclose(np.asarray(K1), np.asarray(K0), atol=2e-5)
    finally:
        at.clear_cache()


def test_tile_key_names_the_bucket_only_when_given():
    assert at.tile_key("newton", n=10, p=5, C=1, backend="tpu") == \
        "newton|tpu|float32|n=10|p=5|C=1"
    assert at.tile_key("newton", n=10, p=5, C=1, backend="tpu", k=64,
                       weighted=True) == \
        "newton|tpu|float32|n=10|p=5|C=1|k=64|w=1"


# ------------------------------------------------------------- prepare once
def test_prepared_call_matches_one_shot():
    """The prepare-once pair gives exactly the one-shot kernel's numbers,
    and an unweighted fit streams no sample weights."""
    Zb, base, xi, W, sw = _case("ising", 20, 5, 45, False)
    stats = bucket_newton_prepare("ising", Zb, base, xi, interpret=True,
                                  tiles=TileConfig(bm=16, kb=8))
    assert len(stats.streams) == 3
    z = stats.streams[0]
    assert z.shape == (5, 24, 48)          # (C*d, k_pad, n_pad): VPU form
    g0, K0 = bucket_newton_stats("ising", Zb, base, xi, W, interpret=True,
                                 tiles=TileConfig(bm=16, kb=8))
    g1, K1 = stats(W)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))
    np.testing.assert_array_equal(np.asarray(K1), np.asarray(K0))
    weighted = bucket_newton_prepare("ising", Zb, base, xi, jnp.ones_like(xi),
                                     interpret=True)
    assert len(weighted.streams) == 4


@pytest.mark.parametrize("use_pallas, expect", [
    (True, {"node_block": 8, "bm": 16, "grid_steps": 3 * 3}),
    (False, {"node_block": None, "bm": None, "grid_steps": None}),
])
def test_trace_event_carries_the_grid(use_pallas, expect, monkeypatch):
    """The ``kernel.bucket_newton_stats`` event says how far the node
    blocking engaged: node block, sample tile and grid steps per call."""
    monkeypatch.setattr(newton, "newton_tile_rule",
                        lambda *a, **kw: (8, 16))
    Zb, base, xi, W, _ = _case("ising", 20, 5, 45, False)
    rec = Recorder(TelemetrySpec())
    with rec.span("fit"):
        stats = bucket_newton_op("ising", Zb, base, xi,
                                 use_pallas=use_pallas, interpret=True)
        stats(W)
    ev = [e for e in rec.events if e["kind"] == "event"
          and e["name"] == "kernel.bucket_newton_stats"]
    assert len(ev) == 1
    tags = ev[0]["tags"]
    assert {key: tags[key] for key in expect} == expect
    assert tags["shape"] == (20, 1, 5, 45)


# ------------------------------------------------------ through the solvers
def _fit_both_paths(monkeypatch, fit):
    """``fit()`` on the default CPU path, then with the bucket solvers
    traced onto the Pallas kernel (interpret mode)."""
    from repro.core import batched
    from repro.kernels.cl import ops
    batched.clear_bucket_solver_caches()
    default = fit()
    monkeypatch.setattr(ops, "default_kernel_path", lambda *a: "interpret")
    batched.clear_bucket_solver_caches()
    rec = Recorder(TelemetrySpec())
    try:
        with rec.span("fit"):
            kernel = fit()
    finally:
        monkeypatch.undo()
        batched.clear_bucket_solver_caches()
    paths = {e["tags"]["backend"] for e in rec.events
             if e["name"] == "kernel.bucket_newton_stats"}
    assert paths == {"interpret"}
    return default, kernel


@pytest.mark.parametrize("case", ["tree", "split_lattice", "weighted",
                                  "prox"])
def test_bucket_solves_through_the_kernel_match(monkeypatch, case):
    """Whole fits with the kernel prepared once per bucket solve agree
    with the reference path to Newton tolerance: hub and leaf buckets of
    a scale-free tree (both forms), a lattice bucket split into its
    slowest rows, per-node sample weights, and the ADMM prox update."""
    from repro.core import batched
    from repro.core.graphs import grid_graph, scale_free_graph
    n = 96
    g = grid_graph(12, 12) if case == "split_lattice" else \
        scale_free_graph(40, seed=0)
    X = jnp.sign(jax.random.normal(jax.random.PRNGKey(3), (n, g.p)))
    if case == "split_lattice":
        monkeypatch.setattr(batched, "_SPLIT_ROWS", 100)
    if case == "prox":
        p_fits = batched.fit_all_local_batched(g, X, want_influence=False)
        lam = [np.zeros_like(f.theta) for f in p_fits]
        rho = [np.full_like(f.theta, 0.5) for f in p_fits]
        tbar = np.zeros(g.p + len(g.edges))

        def fit():
            return batched.prox_update_batched(g, X, tbar, lam, rho)
    else:
        sw = (jax.random.uniform(jax.random.PRNGKey(4), (g.p, n)) < 0.8
              ).astype(jnp.float32) if case == "weighted" else None

        def fit():
            return [f.theta for f in batched.fit_all_local_batched(
                g, X, sample_weight=sw, want_influence=False)]
    split = batched._SPLIT_ROWS
    default, kernel = _fit_both_paths(monkeypatch, fit)
    assert split < g.p or case != "split_lattice"
    for a, b in zip(default, kernel):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                                   atol=1e-5)
