"""Compiled estimation sessions: one :class:`Plan` -> three verbs.

An :class:`EstimationSession` is a plan *compiled*: the graph's degree
buckets, owner structure, per-node block layouts, and fixed-coordinate
vectors are derived once; the jitted degree-bucket Newton solvers are
keyed by the plan's static configuration (family, singleton policy, Newton
budget, mesh, influence demand), so every verb — and every subsequent call
of the same verb — reuses the same compiled programs. Sessions themselves
are cached per plan (``EstimationSession.for_plan`` / ``plan.session()``):
two equal plans share one session and therefore one solver cache.

The four verbs share that cache:

* ``session.fit(X)``     — batch: per-node local CL fits + every requested
                           one-step combiner;
* ``session.stream()``   — a :class:`StreamingEstimator` bound to the plan
                           (same family, mesh, buffer, Newton budget — its
                           incremental re-fits hit the same solvers);
* ``session.joint(X)``   — ADMM joint MPLE through the batched proximal
                           engine;
* ``session.select(X)``  — structure learning: distributed
                           pseudo-likelihood lasso over candidate edges +
                           support voting (:mod:`repro.structure`),
                           returning a :class:`~repro.structure.
                           StructureResult`.

Each returns (or feeds) a structured :class:`~repro.api.result.
EstimateResult` with wall/compile counters and communication-cost scalars.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.admm import admm_mple_family
from ..core.asymptotics import free_indices, param_owners
from ..core.batched import (bucket_compile_count, degree_buckets,
                            fit_all_local_batched, prox_compile_count)
from ..core.estimators import LocalFit
from ..core.graphs import Graph
from ..telemetry.recorder import make_recorder
from .plan import Plan
from .result import EstimateResult

#: session cache — equal plans (and mesh override) share one compiled
#: session; bounded FIFO so long-lived processes cannot leak sessions
_SESSIONS: Dict[Tuple[Plan, object], "EstimationSession"] = {}
_SESSION_CACHE_MAX = 64


def _resolve_mesh(policy: Optional[str]):
    """Materialize a plan's mesh policy into a jax Mesh (or None)."""
    if policy is None:
        return None
    if policy == "host":
        from ..launch.mesh import make_host_mesh
        return make_host_mesh()
    if policy == "data":
        return jax.make_mesh((len(jax.devices()),), ("data",))
    raise ValueError(f"unknown mesh policy {policy!r}")


class EstimationSession:
    """A compiled :class:`Plan`; see the module docstring.

    Construct through :meth:`for_plan` (or ``plan.session()``) to share
    sessions between equal plans. ``mesh`` overrides the plan's mesh
    *policy* with a concrete ``jax.sharding.Mesh`` (used by the legacy
    shims, which receive mesh objects directly).
    """

    def __init__(self, plan: Plan, mesh=None) -> None:
        self.plan = plan
        self.graph: Graph = plan.graph
        self.family = plan.family_instance
        self.combiners = plan.combiner_instances
        self.mesh = mesh if mesh is not None else _resolve_mesh(plan.mesh)
        self.dtype = jnp.dtype(plan.precision)

        # ---- compile-once plan derivations --------------------------------
        self.buckets = degree_buckets(plan.graph)
        self.owners = param_owners(plan.graph, plan.include_singleton,
                                   self.family)
        self.free = np.asarray(free_indices(plan.graph,
                                            plan.include_singleton,
                                            self.family))
        self.betas = [self.family.beta(plan.graph, i, plan.include_singleton)
                      for i in range(plan.graph.p)]
        n_params = self.family.n_params(plan.graph)
        self.theta_fixed = (np.zeros(n_params, dtype=np.float64)
                            if plan.theta_fixed is None
                            else np.asarray(plan.theta_fixed,
                                            dtype=np.float64))
        #: union of the requested combiners' second-order demands
        self.needs = frozenset().union(*(c.needs for c in self.combiners))
        self.want_influence = "influence" in self.needs
        #: owner slots of shared (multi-owner) parameters — the unit the
        #: communication accounting bills per scheme
        self.shared_owner_slots = sum(
            len(own) for own in self.owners.values() if len(own) > 1)
        self.fit_calls = 0
        #: the plan's telemetry recorder — the shared allocation-free
        #: NULL_RECORDER unless the plan declares a TelemetrySpec; one
        #: long-lived recorder per session, scoped per verb call via
        #: mark()/snapshot()
        self.recorder = make_recorder(plan.telemetry)

    # ----------------------------------------------------------- caching
    @classmethod
    def for_plan(cls, plan: Plan, mesh=None) -> "EstimationSession":
        """The cached session for ``plan`` (creating it on first use).

        Equal plans hash to the same key, so they share one session — and
        with it the derived bucket/owner structures and the jitted solver
        cache entries its verbs have already populated.
        """
        key = (plan, mesh)
        sess = _SESSIONS.get(key)
        if sess is None:
            if len(_SESSIONS) >= _SESSION_CACHE_MAX:
                _SESSIONS.pop(next(iter(_SESSIONS)))
            sess = cls(plan, mesh=mesh)
            _SESSIONS[key] = sess
        return sess

    @property
    def n_buckets(self) -> int:
        """Degree buckets == compiled solver programs per fit variant."""
        return len(self.buckets)

    # ------------------------------------------------------------ helpers
    def _as_samples(self, X) -> jnp.ndarray:
        Xj = jnp.asarray(X, dtype=self.dtype)
        if Xj.dtype != self.dtype:
            # jax silently truncates float64 to float32 when x64 is off —
            # a plan that declares a precision must get it or fail loudly
            raise ValueError(
                f"plan declares precision={self.plan.precision!r} but jax "
                f"produced {Xj.dtype} (enable x64 via JAX_ENABLE_X64=1 or "
                f"jax.config.update('jax_enable_x64', True) to honor "
                f"float64 plans)")
        return Xj

    def _tf(self, dtype) -> jnp.ndarray:
        return jnp.asarray(self.theta_fixed, dtype=dtype)

    def _score_norm(self, theta: np.ndarray, X, n: int) -> float:
        from ..stream.online import pseudo_score
        g = pseudo_score(self.graph, theta, X, n, family=self.family)
        return float(np.linalg.norm(g))

    def one_step_comm(self, n: int) -> Dict[str, int]:
        """Scalars a network transmits per requested scheme — the
        family-block generalization of :mod:`repro.stream.costs`, with the
        per-param message size read from the combiner registry (the single
        source ``Combiner.scalars_per_shared_param``): every owner of every
        shared param ships its estimate (+ weight when the scheme uses
        one); Linear-Opt additionally ships its n influence samples per
        shared slot. The serving tier bills per-tenant comm budgets with
        exactly this accounting (summed over schemes)."""
        from ..stream.costs import one_step_comm_by_scheme
        return one_step_comm_by_scheme(self.shared_owner_slots,
                                       self.plan.combiners, n)

    # backward-compatible private alias
    _one_step_comm = one_step_comm

    def fit_local(self, X, sample_weight=None, warm_start=None,
                  want_influence: Optional[bool] = None,
                  theta_fixed=None, stats=None) -> List[LocalFit]:
        """Per-node local CL fits under this plan (the raw engine call the
        legacy ``fit_all_local`` shim routes through).

        ``theta_fixed`` overrides the plan's fixed coordinates for this
        call only — the shim passes per-call arrays here so a caller
        varying them does not mint a new plan (and session cache entry)
        per value.
        """
        Xj = self._as_samples(X)
        tf = (self._tf(Xj.dtype) if theta_fixed is None
              else jnp.asarray(theta_fixed, Xj.dtype))
        return fit_all_local_batched(
            self.graph, Xj,
            include_singleton=self.plan.include_singleton,
            theta_fixed=tf, n_iter=self.plan.n_iter,
            sample_weight=sample_weight, warm_start=warm_start,
            family=self.family, mesh=self.mesh,
            want_influence=(self.want_influence if want_influence is None
                            else want_influence),
            recorder=self.recorder, stats=stats)

    # -------------------------------------------------------------- verbs
    def fit(self, X, sample_weight=None, warm_start=None) -> EstimateResult:
        """Batch verb: local fits + every requested combiner.

        A warm session re-fit on fresh same-shape data triggers zero new
        solver compilations (the bench's ``session_reuse`` row and
        ``tests/api`` assert this).
        """
        rec = self.recorder
        mark = rec.mark()
        t0 = time.perf_counter()
        c0 = bucket_compile_count()
        stats = {"compile_s": 0.0}
        with rec.span("fit"):
            Xj = self._as_samples(X)
            n = int(Xj.shape[0])
            fits = self.fit_local(Xj, sample_weight=sample_weight,
                                  warm_start=warm_start, stats=stats)
            combined = {}
            for c in self.combiners:
                # the child span names the combiner on the profiler trace
                with rec.span("combine", scheme=c.name), rec.span(c.name):
                    combined[c.name] = c.combine(
                        self.graph, fits,
                        include_singleton=self.plan.include_singleton,
                        theta_fixed=self.theta_fixed, family=self.family)
            theta = combined[self.plan.combiners[0]]
            with rec.span("score"):
                score = self._score_norm(theta, Xj, n)
        c1 = bucket_compile_count()
        self.fit_calls += 1
        comm = self._one_step_comm(n)
        if rec.enabled:
            for scheme, cost in comm.items():
                rec.gauge("comm.scalars_per_round", cost, scheme=scheme)
        return EstimateResult(
            mode="fit", theta=theta, combined=combined, fits=fits,
            n_samples=n, score_norm=score,
            wall_s=time.perf_counter() - t0,
            compile_s=stats["compile_s"],
            new_compiles=(c1 - c0 if c0 >= 0 and c1 >= 0 else -1),
            comm_scalars=comm,
            telemetry=rec.snapshot(mark) if rec.enabled else None)

    def stream(self, capacity: Optional[int] = None):
        """Streaming verb: a :class:`~repro.stream.online.StreamingEstimator`
        bound to this plan — same family, mesh, fixed coordinates, and
        Newton budget, so its warm-started incremental re-fits hit the very
        bucket solvers ``fit`` compiled (and vice versa)."""
        from ..stream.online import StreamingEstimator
        return StreamingEstimator(
            self.graph, include_singleton=self.plan.include_singleton,
            theta_fixed=self.theta_fixed,
            capacity=capacity or self.plan.capacity,
            n_iter=self.plan.n_iter, family=self.family, mesh=self.mesh,
            want_influence=self.want_influence,
            window=self.plan.stream_window,
            discount=self.plan.stream_discount,
            recorder=self.recorder)

    def simulate(self, pool, **overrides):
        """An event-driven :class:`~repro.stream.simulator.StreamSimulator`
        configured from this plan (see ``StreamSimulator.from_plan``);
        ``overrides`` win, including an explicit ``mesh=``."""
        from ..stream.simulator import StreamSimulator
        overrides.setdefault("mesh", self.mesh)
        overrides.setdefault("telemetry", self.recorder)
        return StreamSimulator.from_plan(self.plan, pool, **overrides)

    def joint(self, X, sample_weight=None) -> EstimateResult:
        """Joint verb: ADMM MPLE (Sec. 3.2) through the batched proximal
        engine — one compiled solve per degree bucket per round, shared
        with ``fit``'s solver cache through the common engine."""
        rec = self.recorder
        mark = rec.mark()
        t0 = time.perf_counter()
        c0 = bucket_compile_count()
        stats = {"compile_s": 0.0}
        with rec.span("joint"):
            Xj = self._as_samples(X)
            n = int(Xj.shape[0])
            plan = self.plan
            fits = None
            if plan.admm_init != "zero":
                fits = self.fit_local(Xj, sample_weight=sample_weight,
                                      want_influence=False, stats=stats)
            res = admm_mple_family(
                self.graph, Xj, n_iters=plan.admm_iters,
                init=plan.admm_init, fits=fits,
                include_singleton=plan.include_singleton,
                theta_fixed=self.theta_fixed,
                newton_iters=plan.admm_newton_iters, family=self.family,
                mesh=self.mesh, sample_weight=sample_weight,
                rho0=plan.admm_rho, recorder=self.recorder, stats=stats)
            theta = res.trajectory[-1]
            with rec.span("score"):
                score = self._score_norm(theta, Xj, n)
        c1 = bucket_compile_count()
        comm = plan.admm_iters * 2 * sum(len(b) for b in self.betas)
        if rec.enabled:
            rec.gauge("comm.scalars_per_round", comm, scheme="admm")
        return EstimateResult(
            mode="joint", theta=theta, combined={"admm": theta}, fits=fits,
            n_samples=n, score_norm=score,
            wall_s=time.perf_counter() - t0,
            compile_s=stats["compile_s"],
            new_compiles=(c1 - c0 if c0 >= 0 and c1 >= 0 else -1),
            comm_scalars={"admm": comm},
            trajectory=res.trajectory, primal_residual=res.primal_residual,
            telemetry=rec.snapshot(mark) if rec.enabled else None)

    def select(self, X, spec=None) -> "StructureResult":
        """Structure verb: estimate the GRAPH by distributed
        pseudo-likelihood lasso + support voting (:mod:`repro.structure`).

        Runs group-lasso neighborhood selection over a candidate edge set
        (``spec.policy``) along a warm-started descending lambda path —
        every ADMM round reuses the batched proximal engine, so the whole
        path compiles exactly one prox program per degree bucket of the
        candidate graph — picks lambda by EBIC, and reconciles the two
        endpoints' verdicts per candidate edge through the plan's vote
        rule. ``spec`` overrides ``plan.structure`` for this call;
        with neither, :class:`~repro.structure.StructureSpec` defaults
        apply. Note the plan's ``graph`` is NOT assumed correct — it only
        sizes the problem (p nodes); the candidate policy decides which
        edges are searched.
        """
        from ..stream.costs import structure_vote_scalars
        from ..structure import (StructureSpec, StructureResult,
                                 auto_lambda_grid, candidate_graph,
                                 debias_to_support, ebic_scores,
                                 edge_supports, get_vote_rule, lasso_path,
                                 reconcile)
        if spec is None:
            spec = self.plan.structure or StructureSpec()
        elif isinstance(spec, dict):
            spec = StructureSpec.from_dict(spec)
        rule = get_vote_rule(spec.vote)
        rec = self.recorder
        mark = rec.mark()
        t0 = time.perf_counter()
        c0_fit = bucket_compile_count()
        c0_prox = prox_compile_count()
        stats = {"compile_s": 0.0}
        family = self.family
        C = family.block_dim
        lead = 1 if self.plan.include_singleton else 0
        with rec.span("select"):
            Xj = self._as_samples(X)
            Xnp = np.asarray(Xj, dtype=np.float64)
            n, p = Xnp.shape
            if p != self.graph.p:
                raise ValueError(f"X has {p} columns; plan graph has "
                                 f"p={self.graph.p} nodes")

            with rec.span("screen", policy=spec.policy):
                gc = candidate_graph(spec, p, X=Xnp, family=family)
            # the plan's fixed coordinates remapped onto the candidate
            # graph: node blocks carry over, candidate-edge blocks are free
            tf_c = np.zeros(family.n_params(gc))
            tf_c[: p * C] = self.theta_fixed[: p * C]
            tf_cj = jnp.asarray(tf_c, Xj.dtype)

            lambdas = spec.lambdas or auto_lambda_grid(gc, Xnp, family, spec)

            # the dense (unpenalized) fit on the candidate graph: it pins
            # the path's lambda == 0 end to the fit verb, supplies the
            # weighted vote's sandwich-variance masses, and debiases the
            # EBIC likelihoods (shrunk iterates would drag selection
            # dense). Same engine call as session.fit, so a candidate
            # graph equal to the plan graph reuses its compiled programs.
            with rec.span("dense_fit"):
                fits_c = fit_all_local_batched(
                    gc, Xj,
                    include_singleton=self.plan.include_singleton,
                    theta_fixed=tf_cj, n_iter=self.plan.n_iter,
                    family=family, mesh=self.mesh,
                    want_influence=self.want_influence,
                    recorder=rec, stats=stats)
            dense_thetas = [np.asarray(f.theta, dtype=np.float64)
                            for f in fits_c]

            with rec.span("path", n_lambdas=len(lambdas)):
                path = lasso_path(
                    gc, Xj, lambdas, spec, family,
                    include_singleton=self.plan.include_singleton,
                    theta_fixed=tf_cj, dense_thetas=dense_thetas,
                    mesh=self.mesh, recorder=rec, stats=stats)
                ebic = ebic_scores(gc, Xnp, path, family, spec,
                                   self.plan.include_singleton, tf_c,
                                   debias_thetas=dense_thetas)

            with rec.span("vote", rule=rule.name):
                # per-endpoint vote masses: inverse sandwich variance of
                # the edge block (the combiner registry's second-order
                # info, computed by the same engine)
                mass = np.ones((p, gc.m))
                if rule.needs_mass:
                    for i in range(p):
                        ks = gc.incident_edges(i)
                        dv = np.diag(np.asarray(fits_c[i].V))
                        for idx, k in enumerate(ks):
                            blk = dv[(lead + idx) * C:(lead + idx + 1) * C]
                            mass[i, k] = 1.0 / max(float(np.mean(blk)),
                                                   1e-12)
                I = np.array([e[0] for e in gc.edges], dtype=np.int64)
                J = np.array([e[1] for e in gc.edges], dtype=np.int64)
                ar = np.arange(gc.m)
                keeps, margins_l, sizes = [], [], []
                for zs in path:
                    sup = edge_supports(gc, zs, C, lead)
                    keep, margin = reconcile(
                        sup[I, ar], sup[J, ar], rule,
                        mass_a=mass[I, ar], mass_b=mass[J, ar])
                    keeps.append(keep)
                    margins_l.append(margin)
                    sizes.append(int(keep.sum()))
                lsel = int(np.argmin(ebic))
                support = tuple(e for e, k in zip(gc.edges, keeps[lsel])
                                if k)
            comm = structure_vote_scalars(gc.m, rule.name)
            if rec.enabled:
                rec.gauge("structure.candidate_edges", gc.m)
                rec.gauge("structure.support_size", len(support))
                rec.gauge("comm.scalars_per_round", comm,
                          scheme=f"vote_{rule.name}")
        c1_fit = bucket_compile_count()
        c1_prox = prox_compile_count()
        path_compiles = (c1_prox - c0_prox
                         if c0_prox >= 0 and c1_prox >= 0 else -1)
        new_compiles = (path_compiles + c1_fit - c0_fit
                        if min(c0_fit, c1_fit, path_compiles) >= 0 else -1)
        return StructureResult(
            support=support, graph=Graph(p, support),
            candidate_edges=gc.edges, vote_rule=rule.name,
            margins=margins_l[lsel], lambdas=tuple(lambdas),
            lambda_selected=float(lambdas[lsel]), ebic=ebic,
            support_sizes=tuple(sizes),
            thetas=debias_to_support(gc, path[lsel], dense_thetas, C, lead),
            n_samples=n,
            comm_scalars=comm, wall_s=time.perf_counter() - t0,
            compile_s=stats["compile_s"], path_compiles=path_compiles,
            new_compiles=new_compiles,
            telemetry=rec.snapshot(mark) if rec.enabled else None)

    def __repr__(self) -> str:
        return (f"EstimationSession(family={self.plan.family!r}, "
                f"p={self.graph.p}, m={self.graph.m}, "
                f"buckets={self.n_buckets}, "
                f"combiners={list(self.plan.combiners)}, "
                f"mesh={self.plan.mesh!r}, fit_calls={self.fit_calls})")


def compile_plan(plan: Plan, mesh=None) -> EstimationSession:
    """Functional alias for ``EstimationSession.for_plan`` (cached)."""
    return EstimationSession.for_plan(plan, mesh=mesh)
