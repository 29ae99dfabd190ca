"""Regenerate the golden-value regression fixture for the estimator stack.

Freezes seeded outputs of ``fit_all_local_batched`` (per-node local thetas),
``consensus.combine`` (all four one-step weighting schemes) and the batched
ADMM primal update ``prox_update_batched`` (against a flat consensus
iterate, and against per-node consensus views with warm starts) on a small
grid-graph Ising problem into ``tests/core/golden_estimators.json``;
``tests/core/test_golden.py`` asserts future runs reproduce them to 1e-10,
catching silent numeric drift in refactors of the batched engine, the
Gauss-Jordan solver, or the vectorized combiner.

Run from the repo root after an *intentional* numeric change:

    PYTHONPATH=src python tools/gen_golden.py
"""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import numpy as np  # noqa: E402

import repro.core as C  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "tests", "core", "golden_estimators.json")

#: the frozen scenario — change it only together with the fixture
CONFIG = {"graph": "grid_graph(2, 3)", "model_key": 11, "sample_key": 12,
          "sigma_pair": 0.5, "sigma_single": 0.3, "n": 400,
          "schemes": ["uniform", "diagonal", "optimal", "max"],
          "prox": {"seed": 13, "rho": 0.5, "lam_scale": 0.05,
                   "view_scale": 0.1, "n_iter": 15}}


def compute():
    g = C.grid_graph(2, 3)
    m = C.random_model(g, CONFIG["sigma_pair"], CONFIG["sigma_single"],
                       jax.random.PRNGKey(CONFIG["model_key"]))
    X = C.exact_sample(m, CONFIG["n"],
                       jax.random.PRNGKey(CONFIG["sample_key"]))
    fits = C.fit_all_local(g, X, method="batched")
    combined = {sch: C.combine(g, fits, sch) for sch in CONFIG["schemes"]}
    payload = {
        "config": CONFIG,
        "theta_star": np.asarray(m.theta, dtype=np.float64).tolist(),
        "local_theta": [np.asarray(f.theta, dtype=np.float64).tolist()
                        for f in fits],
        "local_vdiag": [np.diag(f.V).astype(np.float64).tolist()
                        for f in fits],
        "combine": {sch: np.asarray(t, dtype=np.float64).tolist()
                    for sch, t in combined.items()},
    }
    payload.update(_prox_updates(g, X, fits, combined["diagonal"]))
    return payload


def _prox_updates(g, X, fits, theta_bar):
    """The ADMM primal update from the diagonal combine: once against the
    flat iterate, once against seeded per-node views warm-started at the
    local fits."""
    cfg = CONFIG["prox"]
    rng = np.random.RandomState(cfg["seed"])
    betas = [np.asarray(g.beta(i)) for i in range(g.p)]
    lambdas = [cfg["lam_scale"] * rng.randn(len(b)) for b in betas]
    rhos = [np.full(len(b), cfg["rho"]) for b in betas]
    views = [theta_bar[b] + cfg["view_scale"] * rng.randn(len(b))
             for b in betas]
    flat = C.prox_update_batched(g, X, theta_bar, lambdas, rhos,
                                 n_iter=cfg["n_iter"])
    viewed = C.prox_update_batched(g, X, views, lambdas, rhos,
                                   thetas0=[f.theta for f in fits],
                                   n_iter=cfg["n_iter"])
    return {key: [np.asarray(t, dtype=np.float64).tolist() for t in out]
            for key, out in (("prox_flat", flat), ("prox_views", viewed))}


def main():
    payload = compute()
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
