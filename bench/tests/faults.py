"""Faults planted under the timed path, for the tests that see a run's
``correct`` come out false. Each is a context manager that patches the
program while a whole run is driven."""
import contextlib
import dataclasses

import numpy as np

import repro.api.session as session_mod
import repro.core.batched as batched


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def answer_altered():
    """One node's local estimate is moved by 1e-3 where it is produced."""
    inner = session_mod.fit_all_local_batched

    def fit(*a, **k):
        fits = inner(*a, **k)
        fits[0] = dataclasses.replace(fits[0], theta=fits[0].theta + 1e-3)
        return fits
    return _patched(session_mod, "fit_all_local_batched", fit)


def prox_answer_altered():
    """Every ADMM round, one node's proximal update is moved by 1e-3."""
    inner = batched.prox_update_batched

    def prox(*a, **k):
        out = inner(*a, **k)
        out[0] = np.asarray(out[0]) + 1e-3
        return out
    return _patched(batched, "prox_update_batched", prox)


@contextlib.contextmanager
def half_batch():
    """Local fits and proximal updates see only the first half of the
    rows (their means are taken over the rest)."""
    fit_local = session_mod.EstimationSession.fit_local
    prox = batched.prox_update_batched

    def half_fit(self, X, *a, **k):
        return fit_local(self, X[: X.shape[0] // 2], *a, **k)

    def half_prox(graph, X, *a, **k):
        return prox(graph, X[: X.shape[0] // 2], *a, **k)
    with _patched(session_mod.EstimationSession, "fit_local", half_fit), \
            _patched(batched, "prox_update_batched", half_prox):
        yield


def state_unchanged():
    """ADMM's proximal step returns the iterate it was given."""
    inner = batched.prox_update_batched

    def stuck(graph, X, theta_bar, lambdas, rhos, thetas0=None, **k):
        if thetas0 is None:
            return inner(graph, X, theta_bar, lambdas, rhos, **k)
        return [np.asarray(t) for t in thetas0]
    return _patched(batched, "prox_update_batched", stuck)
