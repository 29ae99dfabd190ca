"""Golden-value regression pins for the estimator stack.

``tests/core/golden_estimators.json`` (regenerated only deliberately via
``tools/gen_golden.py``) freezes seeded outputs of the batched local-fit
engine, all four one-step consensus schemes and the batched ADMM primal
update on a small grid-graph Ising problem. Reproducing them to 1e-10
catches *silent* numeric drift — a changed einsum association, a
reordered reduction, an accidental dtype downgrade — that
tolerance-based correctness tests would absorb.
"""
import json
import os

import numpy as np
import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_estimators.json")
ATOL = 1e-10


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recomputed():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import gen_golden
    finally:
        sys.path.pop(0)
    return gen_golden.compute()


def test_scenario_is_the_frozen_one(golden, recomputed):
    assert recomputed["config"] == golden["config"]
    np.testing.assert_allclose(recomputed["theta_star"],
                               golden["theta_star"], atol=ATOL)


@pytest.mark.parametrize("key", ["local_theta", "local_vdiag", "prox_flat",
                                 "prox_views"])
def test_batched_local_fits_bitstable(golden, recomputed, key):
    """Per-node outputs of the batched engine: the local fits' thetas and
    variance diagonals, and the ADMM primal update against a flat
    consensus iterate and against warm-started per-node views."""
    assert len(recomputed[key]) == len(golden[key])
    for got, want in zip(recomputed[key], golden[key]):
        np.testing.assert_allclose(got, want, atol=ATOL, err_msg=key)


def test_combine_all_schemes_bitstable(golden, recomputed):
    assert set(recomputed["combine"]) == set(golden["combine"])
    for sch, want in golden["combine"].items():
        np.testing.assert_allclose(recomputed["combine"][sch], want,
                                   atol=ATOL, err_msg=sch)
