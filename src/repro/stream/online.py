"""Online local estimators: incremental, warm-started re-fits over a stream.

Each sensor's conditional-likelihood M-estimator (paper Eq. 3) is an average
over its observed samples, so as chunks arrive the criterion changes but the
optimum moves only O(new/total). :class:`StreamingEstimator` exploits that:
it pools arrivals into a shape-stable :class:`~repro.stream.buffer.
SampleBuffer`, tracks how far into the pool each sensor has seen (prefix
counts), and re-fits *all* nodes through the degree-bucketed batched engine
with per-node 0/1 observation masks and the previous thetas as Newton warm
starts — an incremental re-fit is a couple of damped Newton steps on one
already-compiled program per bucket, not a from-scratch solve. The whole
bank is **family-generic**: pass any registered
:class:`~repro.core.families.base.ModelFamily` and the same machinery
streams Gaussian MRF or Potts estimation.

:func:`pseudo_score` is the observer-side any-time diagnostic: the exact
gradient of the average pseudo-likelihood at an arbitrary theta. Every
family whose ``kernel_kind`` has a registered epilogue in the fused CL
kernel subsystem (``repro.kernels.cl`` — Ising, Gaussian and the
multi-channel Potts all ship one) runs in one fused pass over the padded
buffer; families without an epilogue fall back to the autodiff reference
score on the live rows. Its norm shrinking toward zero is a model-free
convergence signal for whatever consensus estimate is being traced.

Scale-out: both :class:`StreamingEstimator` and
:class:`~repro.stream.simulator.StreamSimulator` take a ``mesh`` kwarg that
routes every incremental re-fit through the batched engine's
shard_map-over-mesh path (bucket nodes sharded along the mesh's ``data``
axis; numerically identical on a one-device mesh).
"""
from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from ..core.batched import fit_all_local_batched
from ..core.consensus import TRUST_RADIUS
from ..telemetry.recorder import NULL_RECORDER
from ..core.estimators import LocalFit
from ..core.families import ISING
from ..core.graphs import Graph
from ..kernels.cl.epilogues import get_epilogue
from ..kernels.cl.family import fused_pseudo_score
from .buffer import SampleBuffer


class StreamingEstimator:
    """Bank of all p per-node online CL estimators over a shared pool.

    The pool model: the environment draws i.i.d. samples x_1, x_2, ...;
    sensor i has observed the first ``counts[i]`` of them (sensors sample at
    different rates, so counts are heterogeneous). ``refit()`` updates every
    node's local fit to its current prefix. ``family`` selects the model
    family (default Ising).

    Prefer obtaining instances through the estimation-plan API —
    ``repro.api.Plan(...).session().stream()`` — which binds family, mesh,
    fixed coordinates, capacity, and Newton budget to one declarative plan
    (and shares the compiled bucket solvers with the session's batch/joint
    verbs); direct construction remains supported as the legacy path.
    """

    def __init__(self, graph: Graph, include_singleton: bool = True,
                 theta_fixed: Optional[np.ndarray] = None,
                 capacity: int = 64, n_iter: int = 40,
                 family=None, mesh=None,
                 want_influence: bool = True,
                 window: Optional[int] = None,
                 discount: Optional[float] = None,
                 recorder=None) -> None:
        if window is not None and int(window) < 1:
            raise ValueError(
                f"sliding window must be >= 1 sample (None disables it), "
                f"got {window!r}")
        if discount is not None and not (0.0 < float(discount) <= 1.0):
            raise ValueError(
                f"discount must be in (0.0, 1.0] (1.0 = no forgetting, "
                f"None disables it), got {discount!r}")
        #: drift-tracking re-fit windows — see SampleBuffer.window_weights
        self.window = None if window is None else int(window)
        self.discount = None if discount is None else float(discount)
        #: telemetry recorder (see :mod:`repro.telemetry`); the shared
        #: allocation-free NULL_RECORDER unless an owner (session or
        #: simulator) injects a live one
        self.recorder = NULL_RECORDER if recorder is None else recorder
        self.graph = graph
        self.family = ISING if family is None else family
        self.mesh = mesh
        #: False skips the (n, d) per-sample influence stacks on every
        #: re-fit — none of the streamable one-step schemes read them, so
        #: plan-bound streams and the simulator opt out (LocalFit.s then
        #: has zero rows); the default keeps the legacy full fits
        self.want_influence = want_influence
        self.include_singleton = include_singleton
        n_params = self.family.n_params(graph)
        self.theta_fixed = (np.zeros(n_params, dtype=np.float64)
                            if theta_fixed is None
                            else np.asarray(theta_fixed, dtype=np.float64))
        self.n_iter = n_iter
        self.buffer = SampleBuffer(graph.p, capacity=capacity)
        self.counts = np.zeros(graph.p, dtype=np.int64)
        self.versions = np.zeros(graph.p, dtype=np.int64)
        self.fits: Optional[List[LocalFit]] = None
        self._warm: Optional[List[np.ndarray]] = None
        self._fit_counts = np.full(graph.p, -1, dtype=np.int64)

    # ------------------------------------------------------------ ingestion
    def extend_pool(self, rows) -> None:
        """Append environment samples to the shared pool (nobody has seen
        them yet until ``advance``/``ingest`` says so)."""
        self.buffer.append(rows)

    def advance(self, counts: np.ndarray) -> None:
        """Move per-node seen-counts forward (monotone, clipped to pool)."""
        counts = np.minimum(np.asarray(counts, dtype=np.int64), self.buffer.n)
        if np.any(counts < self.counts):
            raise ValueError("seen-counts must be monotone nondecreasing")
        self.counts = counts

    def ingest(self, rows) -> None:
        """Chunked convenience path: append rows and let every node see the
        whole pool — feeding the same data in k chunks or at once yields the
        same fits (to Newton tolerance)."""
        self.extend_pool(rows)
        self.advance(np.full(self.graph.p, self.buffer.n, dtype=np.int64))

    @property
    def n_pool(self) -> int:
        return self.buffer.n

    @property
    def effective_counts(self) -> np.ndarray:
        """Per-node effective sample sizes: the total fit weight each node
        places on the pool. Equal to ``counts`` without windows; the
        window/discount-weighted mass otherwise — the right ``n`` for
        1/n variance scalings under forgetting."""
        if self.window is None and self.discount is None:
            return self.counts.astype(np.float64)
        return self.buffer.window_weights(
            self.counts, self.window, self.discount).sum(
                axis=1).astype(np.float64)

    # ------------------------------------------------------------ durability
    def state_dict(self):
        """Full restorable state as (arrays, json_meta) — pool, per-node
        prefix counts/versions, warm starts, and the fitted LocalFit bank —
        everything a fresh estimator (constructed with the same
        configuration) needs to continue bit-identically."""
        arrays = {
            "est/pool": self.buffer.data.copy(),
            "est/counts": self.counts.copy(),
            "est/versions": self.versions.copy(),
            "est/fit_counts": self._fit_counts.copy(),
            "est/theta_fixed": self.theta_fixed.copy(),
        }
        meta = {
            "n": int(self.buffer.n),
            "window": self.window,
            "discount": self.discount,
            "warm": [w is not None for w in (self._warm or [])],
            "betas": None,
        }
        if self._warm is not None:
            for i, w in enumerate(self._warm):
                if w is not None:
                    arrays[f"est/warm_{i}"] = np.asarray(w)
        if self.fits is not None:
            meta["betas"] = [list(map(int, f.beta)) for f in self.fits]
            for f in self.fits:
                for part in ("theta", "H", "J", "V", "s"):
                    arrays[f"est/fit{f.i}_{part}"] = np.asarray(
                        getattr(f, part))
        return arrays, meta

    def load_state(self, arrays, meta) -> None:
        """Inverse of :meth:`state_dict`, in place."""
        pool = np.asarray(arrays["est/pool"])
        self.buffer._X = pool.copy()
        self.buffer.n = int(meta["n"])
        self.counts = np.asarray(arrays["est/counts"]).copy()
        self.versions = np.asarray(arrays["est/versions"]).copy()
        self._fit_counts = np.asarray(arrays["est/fit_counts"]).copy()
        self.theta_fixed = np.asarray(arrays["est/theta_fixed"]).copy()
        self.window = meta["window"]
        self.discount = meta["discount"]
        warm_flags = meta.get("warm") or []
        if warm_flags:
            self._warm = [
                np.asarray(arrays[f"est/warm_{i}"]).copy() if present
                else None for i, present in enumerate(warm_flags)]
        else:
            self._warm = None
        betas = meta.get("betas")
        if betas is None:
            self.fits = None
        else:
            self.fits = [
                LocalFit(i=i, beta=list(b),
                         theta=np.asarray(arrays[f"est/fit{i}_theta"]),
                         H=np.asarray(arrays[f"est/fit{i}_H"]),
                         J=np.asarray(arrays[f"est/fit{i}_J"]),
                         V=np.asarray(arrays[f"est/fit{i}_V"]),
                         s=np.asarray(arrays[f"est/fit{i}_s"]))
                for i, b in enumerate(betas)]

    # --------------------------------------------------------------- fitting
    def refit(self) -> List[LocalFit]:
        """Warm-started weighted re-fit of every node at its current prefix.

        Bumps a node's version when its data actually changed since its last
        fit, so a network layer can broadcast only fresh fits. A no-op call
        (no counts moved, e.g. a stalled arrival process) returns the cached
        fits without paying for a solve.
        """
        if self.fits is not None and np.array_equal(self.counts,
                                                    self._fit_counts):
            return self.fits
        rec = self.recorder
        masks = self.buffer.window_weights(self.counts, self.window,
                                           self.discount)
        with rec.span("refit"):
            fits = fit_all_local_batched(
                self.graph, jnp.asarray(self.buffer.data),
                include_singleton=self.include_singleton,
                theta_fixed=jnp.asarray(self.theta_fixed,
                                        dtype=self.buffer.data.dtype),
                n_iter=self.n_iter,
                sample_weight=jnp.asarray(masks),
                warm_start=self._warm,
                family=self.family, mesh=self.mesh,
                want_influence=self.want_influence,
                recorder=rec)
        if rec.enabled:
            # buffer occupancy + window effective counts at this re-fit
            rec.gauge("stream.buffer_rows", int(self.buffer.n))
            rec.gauge("stream.buffer_capacity",
                      int(self.buffer.data.shape[0]))
            rec.gauge("stream.effective_count_mean",
                      float(self.effective_counts.mean()))
        return self._finish_refit(fits)

    def _finish_refit(self, fits: List[LocalFit]) -> List[LocalFit]:
        """Post-solve bookkeeping shared by :meth:`refit` and the serving
        tier's coalesced dispatch (which solves several estimators' banks
        in one union program and hands each its slice): version bumps for
        nodes whose data changed, prefix-count snapshot, and trust-radius
        warm-start hygiene.
        """
        changed = self.counts != self._fit_counts
        self.versions = self.versions + changed.astype(np.int64)
        self._fit_counts = self.counts.copy()
        # a diverged fit (quasi-separation at small n drives the optimum to
        # infinity; NaN is absorbing in Newton) must not poison every future
        # re-fit through its warm start: from |theta| ~ 1e9 no bounded step
        # schedule returns. Cold-restart nodes outside the same trust radius
        # consensus.combine uses to disqualify owners; once the node has
        # enough data its cold re-fit lands at the now-finite optimum.
        self._warm = [
            f.theta if np.all(np.isfinite(f.theta))
            and np.max(np.abs(f.theta)) <= TRUST_RADIUS else None
            for f in fits]
        self.fits = fits
        return fits

    # ----------------------------------------------------------- diagnostics
    def score_norm(self, theta: np.ndarray,
                   interpret: Optional[bool] = None) -> float:
        """||grad pseudo-loglik(theta)|| over the pooled samples."""
        g = pseudo_score(self.graph, theta, self.buffer.data, self.buffer.n,
                         interpret=interpret, family=self.family)
        return float(np.linalg.norm(g))


def pseudo_score(graph: Graph, theta: np.ndarray, x_pad,
                 n_seen: int, interpret: Optional[bool] = None,
                 family=None, use_pallas: Optional[bool] = None) -> np.ndarray:
    """Exact flat gradient of the average pseudo-likelihood at ``theta``.

    Family-dispatched through the fused CL kernel subsystem: any family
    whose ``kernel_kind`` has a registered epilogue (Ising, Gaussian, and
    multi-channel Potts) runs one fused pass over the (zero-padded) sample
    buffer — the channelized kernel emits the per-sample score residuals r
    and the cross-channel score Gram ``S[c, e] = r_c^T F_e / n``;
    channel-c singleton gradients are live-row means of ``r_c`` and the
    coupling gradient of edge (i, j) is ``S[c, c][i, j] + S[c, c][j, i]``
    (see :func:`repro.kernels.cl.family.fused_pseudo_score`). Families
    without an epilogue fall back to the autodiff reference score over the
    live rows.

    ``use_pallas=None`` takes the backend default through the dispatch
    layer — the compiled Mosaic kernel on TPU, the XLA-compiled tiled
    twin elsewhere; pass ``use_pallas=True`` to force the Pallas kernel
    body, in which case ``interpret`` chooses interpret vs compiled
    execution (``None`` = compiled where the backend supports it).
    """
    if family is None:
        family = ISING
    theta = np.asarray(theta, dtype=np.float64)
    if n_seen <= 0:
        return np.zeros(family.n_params(graph))
    if get_epilogue(getattr(family, "kernel_kind", None)) is None:
        return family.pseudo_score(graph, theta,
                                   np.asarray(x_pad)[: int(n_seen)])
    return fused_pseudo_score(family, graph, theta, x_pad, n_seen,
                              interpret=interpret, use_pallas=use_pallas)
