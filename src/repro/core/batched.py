"""Batched local-estimator engine: degree-bucketed, vmapped Newton-IRLS,
generalized over exponential-family models.

The paper's local CL estimators (Eq. 3) are p independent node-conditional
GLM fits. The seed implementation fit them in a Python loop — one
separately-jitted solve per node, each recomputing a full autodiff
``jax.hessian`` every Newton iteration. This module exploits the
embarrassing parallelism structurally:

* nodes are grouped into **degree buckets** (degree padded up to the next
  power of four), so XLA compiles one solver per bucket instead of one per
  node;
* within a bucket all k neighbor designs are stacked into a
  ``(k, C, deg, n)`` tensor — C the family's channel count (1 for
  Ising/Gaussian, q-1 for Potts) — and solved simultaneously by batched
  einsum Newton steps;
* gradients and Hessians use each family's **closed-form** per-channel
  score ``r = dl/deta`` and curvature ``kappa = -d2l/deta2`` hooks
  (:class:`repro.core.families.base.ModelFamily`) — logistic
  ``r = 2 x sigma(-2 x eta)``, Gaussian ``r = x - eta`` with constant unit
  curvature (so the "IRLS" is a single weighted least-squares step), and
  multinomial-softmax ``diag(pi) - pi pi'`` cross-channel curvature —
  dropping an autodiff order per iteration relative to ``jax.hessian``;
* Newton systems are solved by a **pure-XLA batched Gauss-Jordan sweep**
  (sign-definite systems need no pivoting), avoiding the per-matrix LAPACK
  dispatch of ``jnp.linalg.solve`` that dominates wall-clock for the tiny
  per-node systems — and the custom-call lowering that dominates compile
  time;
* iteration stops early (``while_loop``) once every node's damped Newton
  step is below tolerance, instead of always burning the full budget.

Padding is exact: padded design columns are zero, so their gradient entries
vanish and the Hessian is block-diagonal with a ``-1`` placeholder on padded
coordinates; the Newton direction on real coordinates is untouched.

Per-node parameters are flat in **coordinate-major block layout**
``[singleton block (C), edge block (C) per incident edge]``, matching
``family.beta``; at C = 1 this is exactly the seed's scalar layout.

Public entry points: :func:`degree_buckets`, :func:`fit_all_local_batched`,
the streaming-ADMM primal update :func:`prox_update_batched`, and the
per-bucket compile-count probe :func:`bucket_compile_count`.

Streaming support (used by :mod:`repro.stream`): ``sample_weight`` lets every
node weight the shared sample pool independently — a 0/1 prefix mask per node
expresses "sensor i has only seen its first n_i rows" without changing array
shapes, so a growing stream stays on one compiled program per (bucket,
capacity); ``warm_start`` seeds Newton at the previous fit so incremental
re-fits converge in a couple of damped steps instead of from scratch.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..kernels.cl.epilogues import get_epilogue
from ..kernels.cl.ops import bucket_newton_stats_op
from ..telemetry.recorder import NULL_RECORDER
from .estimators import LocalFit
from .families import ISING
from .graphs import Graph

# Backtracking candidates for clipped Newton steps, largest first so ties at
# the optimum keep the full step; 0 is the "every direction hurts" escape.
_LS_CAND = np.array([1.0, 0.5, 0.25, 0.125, 0.0625, 0.015625, 0.0],
                    dtype=np.float32)
# Gradient-direction scales tried alongside the Newton candidates: when the
# Hessian is near-singular (saturated fits) the Newton direction can be
# useless at every scale, but a small enough ascent step along the gradient
# of a concave criterion always improves off-optimum — so nodes cannot get
# permanently stuck.
_LS_GRAD = np.array([1.0, 0.25, 0.0625, 0.015625, 0.00390625],
                    dtype=np.float32)


def _backtrack_step(objective, W, dirn, g, max_step):
    """Pick, per node, the best step among scaled Newton and gradient
    candidates by the concave per-node ``objective``; returns (k, d) steps.

    Convention matches the solvers: the update is ``W - step``, so Newton
    candidates are ``s * dirn`` and ascent candidates ``-s * g_unit``.
    """
    k = W.shape[0]
    ncand = jnp.asarray(_LS_CAND, W.dtype)[:, None, None] * dirn[None]
    gnorm = jnp.linalg.norm(g, axis=1, keepdims=True)
    gdir = -g * (max_step / (gnorm + 1e-30))
    gcand = jnp.asarray(_LS_GRAD, W.dtype)[:, None, None] * gdir[None]
    steps = jnp.concatenate([ncand, gcand], axis=0)          # (c, k, d)
    vals = objective(W[None] - steps)
    vals = jnp.where(jnp.isfinite(vals), vals, -jnp.inf)
    best = jnp.argmax(vals, axis=0)                          # (k,)
    return steps[best, jnp.arange(k)]


def _float32_contractions(fn):
    """Trace ``fn`` with every matmul/einsum at ``Precision.HIGHEST``.

    The TPU's default contraction precision rounds float32 operands to
    bfloat16; a float32 plan must mean float32 on every backend. CPU
    contractions are exact at any setting, so CPU results are unchanged.
    """
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


def _pad_degree(deg: int) -> int:
    """Bucket width for a node of degree ``deg``: next power of 4 (min 1).

    Coarser-than-power-of-2 padding trades a little wasted compute inside a
    bucket (at most 4x on zero columns, which the einsums eat on the VPU)
    for fewer distinct shapes, i.e. fewer XLA compilations.
    """
    pad = 1
    while pad < deg:
        pad *= 4
    return pad


@dataclasses.dataclass(frozen=True)
class DegreeBucket:
    """All nodes whose padded degree is ``deg_pad``, with gather metadata."""
    deg_pad: int
    nodes: np.ndarray      # (k,) node indices, ascending
    nbrs: np.ndarray       # (k, deg_pad) neighbor indices, 0-padded
    mask: np.ndarray       # (k, deg_pad) 1.0 on real columns, 0.0 on padding


@functools.lru_cache(maxsize=64)
def _degree_buckets_cached(graph: Graph):
    by_pad: Dict[int, List[int]] = {}
    nbrs_of: Dict[int, List[int]] = {}
    for i in range(graph.p):
        ks = graph.incident_edges(i)
        others = [graph.edges[k][0] if graph.edges[k][1] == i
                  else graph.edges[k][1] for k in ks]
        nbrs_of[i] = others
        by_pad.setdefault(_pad_degree(len(others)), []).append(i)

    buckets = []
    for deg_pad in sorted(by_pad):
        nodes = np.asarray(sorted(by_pad[deg_pad]), dtype=np.int32)
        k = len(nodes)
        nbrs = np.zeros((k, deg_pad), dtype=np.int32)
        mask = np.zeros((k, deg_pad), dtype=np.float32)
        for row, i in enumerate(nodes):
            d = len(nbrs_of[i])
            nbrs[row, :d] = nbrs_of[i]
            mask[row, :d] = 1.0
        buckets.append(DegreeBucket(deg_pad=deg_pad, nodes=nodes,
                                    nbrs=nbrs, mask=mask))
    return tuple(buckets)


def degree_buckets(graph: Graph) -> List[DegreeBucket]:
    """Group nodes by padded degree; neighbor order matches ``node_design``.

    Columns are ordered like ``graph.incident_edges(i)`` (edge order), which
    is what :func:`repro.core.estimators.node_design` and ``family.beta``
    use, so bucketed estimates line up coordinate-for-coordinate with the
    seed per-node solver. Cached per graph (graphs are frozen/hashable).
    """
    return list(_degree_buckets_cached(graph))


def _gauss_jordan_solve(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Batched solve A @ X = B for sign-definite A via Gauss-Jordan.

    A: (k, d, d) uniformly positive- or negative-definite (no pivoting
    needed); B: (k, d, m). Pure jnp ops — one fori_loop of rank-1 updates —
    so it lowers to plain XLA vector code instead of per-matrix LAPACK
    custom calls, which dominate both runtime and compile time for the
    small systems this engine solves.
    """
    d = A.shape[-1]
    M = jnp.concatenate([A, B], axis=2)              # (k, d, d + m)

    def body(i, M):
        piv = M[:, i, :] / M[:, i, i][:, None]       # (k, d + m)
        coef = M[:, :, i]                            # (k, d)
        M = M - coef[:, :, None] * piv[:, None, :]
        return M.at[:, i, :].set(piv)                # pivot row normalized

    M = jax.lax.fori_loop(0, d, body, M)
    return M[:, :, d:]


def _solver_dtype(dtype):
    """Newton/solver state dtype for a design dtype.

    bfloat16 designs keep float32 solver state — the mixed-precision mode
    is load/matmul-side only: bf16 designs contracted against float32
    parameters promote every Gram/score accumulation to float32 (see
    :mod:`repro.kernels.cl.precision`), and the Newton iterate, Hessian
    ridge, and convergence test must not quantize. float32/float64 pass
    through untouched (bit-stable with the goldens).
    """
    dtype = jnp.dtype(dtype)
    return jnp.dtype(jnp.float32) if dtype == jnp.bfloat16 else dtype


def _bucket_design(family, X, nodes, nbrs, mask, offsets,
                   include_singleton: bool):
    """Build the channelized (k, C, d, n) bucket design + targets/masks.

    Shared by the plain and proximal bucket solvers. Returns
    ``(Zb, xi, base, cmask)``: per-channel stacked designs, node samples,
    fixed-singleton block offsets folded into ``base`` (k, C, n), and the
    d-length coordinate mask (all channels of a coordinate share one mask
    entry). ``offsets``: (k, C) fixed singleton blocks.
    """
    C = family.block_dim
    # (n, k, deg_pad, C): family features of the gathered neighbor values
    F = family.edge_features(X[:, nbrs])
    # cast the 0/1 mask to the design dtype so a bf16 design stays bf16
    # (f32/f64 designs see the same promotion as before, bit-identically)
    Zt = jnp.transpose(F, (1, 3, 2, 0)) \
        * mask.astype(F.dtype)[:, None, :, None]
    xi = X[:, nodes].T                                       # (k, n)
    k, _, _, n = Zt.shape

    if include_singleton:
        ones = jnp.ones((k, C, 1, n), Zt.dtype)
        Zb = jnp.concatenate([ones, Zt], axis=2)             # (k, C, d, n)
        cmask = jnp.concatenate(
            [jnp.ones((mask.shape[0], 1), mask.dtype), mask], axis=1)
        base = jnp.zeros((k, C, n), Zt.dtype)
    else:
        Zb = Zt
        cmask = mask
        base = offsets[:, :, None] * jnp.ones((k, C, n), Zt.dtype)
    return Zb, xi, base, cmask


def _flat_coord_mask(cmask: jnp.ndarray, C: int) -> jnp.ndarray:
    """(k, d) coordinate mask -> (k, d*C) flat-parameter mask."""
    k, d = cmask.shape
    return jnp.broadcast_to(cmask[:, :, None], (k, d, C)).reshape(k, d * C)


def _channel_ops(family, Zb, base, xi, sw, weighted, denom):
    """Channelized-GLM contraction closures shared by the plain and proximal
    bucket solvers, all in the flat coordinate-major (k, d*C) layout.

    C == 1 (Ising/Gaussian) keeps the seed's single-channel matmul forms —
    XLA contracts them noticeably faster than the general channelized
    einsums. The branch is static (``block_dim`` is a trace-time constant),
    so each family compiles only its own form.

    Returns ``(score_curvature, grad_vec, curvature_matrix, avg_loglik,
    score_matrix, newton_stats)``: per-sample channel score/curvature at a
    flat W, the flat gradient vector from a channel score, the (k, dC, dC)
    curvature matrix from a channel curvature, the (c, k) per-node average
    loglik of a candidate stack, the (k, dC, n) per-sample score matrix,
    and the fused Newton statistics ``W -> (g_raw, K_raw)``.

    ``newton_stats`` is the per-iteration hot path: for families with a
    registered fused-kernel epilogue (``family.kernel_kind``) it goes
    through :func:`repro.kernels.cl.ops.bucket_newton_stats_op` — the fused
    score + Gram entry emitting both directly in this (k, C, d) bucket
    layout (compiled Pallas on TPU, the bit-identical jnp reference
    elsewhere) without materializing the per-sample residual/curvature
    between contractions; families without an epilogue fall back to the
    closed-form hook closures.
    """
    k, C, d, _ = Zb.shape
    dC = d * C
    Z1 = Zb[:, 0] if C == 1 else None

    def eta_of(W):
        if C == 1:
            return base + jnp.einsum("kdn,kd->kn", Z1, W)[:, None, :]
        return base + jnp.einsum("kcdn,kdc->kcn", Zb, W.reshape(k, d, C))

    def score_curvature(W):
        eta = eta_of(W)
        r = family.dl_deta(eta, xi)                          # (k, C, n)
        kap = family.curvature(eta, xi)                      # (k, C, C, n)
        if weighted:
            r = r * sw[:, None, :]
            kap = kap * sw[:, None, None, :]
        return r, kap

    def grad_vec(r):
        if C == 1:
            return jnp.einsum("kdn,kn->kd", Z1, r[:, 0])
        return jnp.einsum("kcdn,kcn->kdc", Zb, r).reshape(k, dC)

    def curvature_matrix(kap):
        if C == 1:
            return (Z1 * kap[:, 0, 0][:, None, :]) @ jnp.swapaxes(Z1, 1, 2)
        H = jnp.einsum("kcdn,kcen,kefn->kdcfe", Zb, kap, Zb)
        return H.reshape(k, dC, dC)

    def avg_loglik(Ws):
        # per-node average conditional loglik for a (c, k, d*C) stack of
        # candidate parameter points; returns (c, k)
        if C == 1:
            etas = base[None] \
                + jnp.einsum("kdn,akd->akn", Z1, Ws)[:, :, None, :]
        else:
            Wb = Ws.reshape(Ws.shape[0], k, d, C)
            etas = base[None] + jnp.einsum("kcdn,akdc->akcn", Zb, Wb)
        ll = family.loglik_eta(etas, xi[None])
        if weighted:
            ll = ll * sw[None]
        return ll.sum(axis=2) / denom[None, :]

    def score_matrix(r):
        if C == 1:
            return Z1 * r[:, 0][:, None, :]                  # (k, d, n)
        n = Zb.shape[-1]
        return jnp.transpose(Zb * r[:, :, None, :],
                             (0, 2, 1, 3)).reshape(k, dC, n)

    kind = getattr(family, "kernel_kind", None)
    fused_kind = kind if get_epilogue(kind) is not None else None

    def newton_stats(W):
        if fused_kind is not None:
            return bucket_newton_stats_op(fused_kind, Zb, base, xi, W,
                                          sw if weighted else None)
        r, kap = score_curvature(W)
        return grad_vec(r), curvature_matrix(kap)

    return score_curvature, grad_vec, curvature_matrix, avg_loglik, \
        score_matrix, newton_stats


@_float32_contractions
def _solve_bucket_impl(X, nodes, nbrs, mask, offsets, W0, sw,
                       include_singleton: bool, n_iter: int,
                       weighted: bool = False, guarded: bool = False,
                       family=ISING, tol: float = 2e-6,
                       ridge: float = 1e-8, max_step: float = 5.0,
                       want_influence: bool = True,
                       axis_name: Optional[str] = None):
    """Solve every node of one degree bucket in a single XLA program.

    X: (n, p) samples; nodes: (k,); nbrs: (k, deg_pad); mask: (k, deg_pad);
    offsets: (k, C) fixed singleton blocks (used when
    include_singleton=False); W0: (k, d*C) Newton warm start (zeros for a
    cold fit); sw: (k, n) per-node sample weights, only read when
    ``weighted`` — a 0/1 prefix mask lets each node of the bucket see a
    different prefix of a shared streaming pool at fixed array shapes.
    ``family`` (static) supplies the closed-form per-channel score and
    curvature; the Ising default reproduces the seed engine exactly.

    Designs live in (k, C, d, n) layout so the per-iteration Hessian is one
    batched einsum contracting over the contiguous sample axis; for C = 1
    the channel axes collapse and nothing is wasted. ``tol`` (on the damped
    step's inf-norm) is chosen just above the float32 jitter floor: iterating
    past it only bounces around the optimum, which is all the seed's fixed
    40-iteration schedule does after convergence.

    Returns (W, H, J, V, S, I) with leading bucket dimension k and flat
    parameter dimension d*C (coordinate-major blocks); padded coordinates
    are exactly zero in W and carry a ``-1`` placeholder diagonal in the
    Newton system. ``I`` is the (k,) Newton-iteration count the damped
    solve actually used (bucket-wide — the while_loop stops when every
    node's step converged — broadcast per node so it shards like the other
    outputs). A node whose weights sum to zero (nothing observed yet)
    stays at W0 untouched by data: its gradient vanishes and the guarded
    denominator keeps it finite.

    ``axis_name`` names the mesh axis when this runs as one shard of a
    bucket: the convergence test then takes the step's max over all
    shards, so every shard runs the iteration count the whole bucket
    would run on one device and the result does not depend on the
    device count.
    """
    n = X.shape[0]
    Zb, xi, base, cmask = _bucket_design(family, X, nodes, nbrs, mask,
                                         offsets, include_singleton)
    k, C, d, _ = Zb.shape
    dC = d * C
    cdtype = _solver_dtype(Zb.dtype)
    W0 = W0.astype(cdtype)
    eye = jnp.eye(dC, dtype=cdtype)
    # -1 on padded diagonals keeps the (exactly block-diagonal) system
    # uniformly negative definite without touching the real block's
    # Newton direction.
    cflat = _flat_coord_mask(cmask, C)
    pad_diag = (1.0 - cflat)[:, :, None] * eye[None, :, :]
    if weighted:
        denom = jnp.maximum(jnp.sum(sw, axis=1), 1.0)        # (k,)
    else:
        denom = jnp.full((k,), float(n), cdtype)

    score_curvature, grad_vec, curvature_matrix, objective, score_matrix, \
        newton_stats = _channel_ops(family, Zb, base, xi, sw, weighted, denom)

    def cond(carry):
        _, it, delta = carry
        return (it < n_iter) & (delta > tol)

    def newton_step(carry):
        W, it, _ = carry
        g_raw, K_raw = newton_stats(W)           # fused score + Gram
        g = g_raw / denom[:, None]
        H = -K_raw / denom[:, None, None] \
            - ridge * eye[None, :, :] - pad_diag
        dirn = _gauss_jordan_solve(H, g[..., None])[..., 0]  # (k, dC)
        # an untrusted direction: non-finite (curvature underflow at a
        # saturated point makes the solve blow up) or clipped (outside
        # Newton's trust region). NaN directions are zeroed so they cannot
        # poison the bucket-wide convergence check.
        finite = jnp.all(jnp.isfinite(dirn), axis=1, keepdims=True)
        dirn = jnp.where(finite, dirn, 0.0)
        norm = jnp.linalg.norm(dirn, axis=1, keepdims=True)
        untrusted = (norm > max_step) | ~finite
        dirn = jnp.where(norm > max_step,
                         dirn * (max_step / (norm + 1e-30)), dirn)
        if guarded:
            # An untrusted direction means the quadratic model failed there
            # — a full clipped step from a saturated warm start can land
            # where the next clipped step points exactly back (a period-2
            # cycle), and a near-singular Hessian can make the direction
            # useless at any scale. Guard with a per-node backtracking
            # search over Newton + gradient candidates on the concave CL
            # objective. Only warm-started solves compile this branch: the
            # pathologies need a saturated starting point, and cold starts
            # from zero (the benchmarked hot path) never produce one.
            step = jax.lax.cond(
                jnp.any(untrusted),
                lambda: _backtrack_step(objective, W, dirn, g, max_step),
                lambda: dirn)
        else:
            step = dirn
        delta = jnp.max(jnp.abs(step))
        if axis_name is not None:
            delta = jax.lax.pmax(delta, axis_name)
        return W - step, it + 1, delta

    W, iters, _ = jax.lax.while_loop(cond, newton_step, (W0, 0, jnp.inf))
    I = jnp.full((k,), iters, dtype=jnp.int32)

    # sandwich diagnostics at W_hat (closed forms again; no autodiff).
    # Under 0/1 weights the masked-out samples' scores are zeroed, so their
    # rows of S are exactly zero and J/H average only the live samples;
    # consumers that normalize influence columns by the row count (the
    # "optimal" combiner) should use the live count, not the buffer size.
    r, kap = score_curvature(W)
    G = score_matrix(r)                                      # (k, dC, n)
    J = G @ jnp.swapaxes(G, 1, 2) / denom[:, None, None]
    H = curvature_matrix(kap) / denom[:, None, None]         # = -hessian
    Hreg = H + 1e-9 * eye[None, :, :] + pad_diag
    Hinv = _gauss_jordan_solve(Hreg, jnp.broadcast_to(eye, Hreg.shape))
    V = Hinv @ J @ jnp.swapaxes(Hinv, 1, 2)
    if want_influence:
        S = jnp.swapaxes(G, 1, 2) @ jnp.swapaxes(Hinv, 1, 2)  # (k, n, dC)
    else:
        # only the Linear-Opt combiner reads the (k, n, dC) per-sample
        # influence stack; a session whose combiners never request
        # "influence" skips materializing it (static branch)
        S = jnp.zeros((k, 0, dC), cdtype)
    return W, H, J, V, S, I


@functools.partial(jax.jit,
                   static_argnames=("include_singleton", "n_iter", "weighted",
                                    "guarded", "family", "want_influence"))
def _solve_bucket(X, nodes, nbrs, mask, offsets, W0, sw,
                  include_singleton: bool, n_iter: int, weighted: bool = False,
                  guarded: bool = False, family=ISING, tol: float = 2e-6,
                  ridge: float = 1e-8, max_step: float = 5.0,
                  want_influence: bool = True):
    """Single-device bucket solve (jitted :func:`_solve_bucket_impl`)."""
    return _solve_bucket_impl(X, nodes, nbrs, mask, offsets, W0, sw,
                              include_singleton, n_iter, weighted, guarded,
                              family, tol, ridge, max_step, want_influence)


def _mesh_data_size(mesh) -> int:
    """Size of the mesh's ``data`` axis; clear error when there isn't one."""
    if "data" not in mesh.axis_names:
        raise ValueError(
            f"batched engine shards degree buckets along a 'data' mesh axis;"
            f" mesh has axes {tuple(mesh.axis_names)}")
    return int(mesh.shape["data"])


@functools.partial(jax.jit,
                   static_argnames=("include_singleton", "n_iter", "weighted",
                                    "guarded", "family", "mesh",
                                    "want_influence"))
def _solve_bucket_sharded(X, nodes, nbrs, mask, offsets, W0, sw,
                          include_singleton: bool, n_iter: int,
                          weighted: bool = False, guarded: bool = False,
                          family=ISING, mesh=None,
                          want_influence: bool = True):
    """Mesh-sharded bucket solve: nodes split along the ``data`` axis.

    The bucket's k per-node problems are embarrassingly parallel, so each
    device solves its contiguous slice of the (padded) node axis against
    the replicated sample pool. The only collective is one scalar max per
    Newton iteration, which keeps every shard on the bucket-wide stopping
    iteration (so the result does not depend on the device count). On a
    one-device
    mesh (the host mesh) the single shard is the whole bucket and the
    computation is identical to :func:`_solve_bucket` op for op, which is
    what makes the single-device fallback numerically exact. The caller
    pads the node axis to a multiple of the shard count
    (:func:`_pad_bucket_rows`).
    """
    body = functools.partial(
        _solve_bucket_impl, include_singleton=include_singleton,
        n_iter=n_iter, weighted=weighted, guarded=guarded, family=family,
        want_influence=want_influence, axis_name="data")
    data = P("data")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), data, data, data, data, data,
                  data if weighted else P()),
        out_specs=(data, data, data, data, data, data),
        check_vma=False,
    )(X, nodes, nbrs, mask, offsets, W0, sw)


def _pad_bucket_rows(shards: int, *arrays):
    """Zero-pad each array's leading (bucket-node) axis to a multiple of
    ``shards`` so shard_map can split it evenly. Padded rows are inert
    dummy problems (zero design mask / zero weights) whose results the
    caller slices off."""
    k = arrays[0].shape[0]
    pad = (-k) % shards
    if pad == 0:
        return arrays
    out = []
    for a in arrays:
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        out.append(jnp.pad(a, widths))
    return tuple(out)


def bucket_compile_count() -> int:
    """Bucket-solver compilations since the last ``clear_cache()``, summed
    over the plain AND mesh-sharded fit solvers — so compile-reuse
    invariants (cold == #buckets, warm == 0) hold for mesh-policy sessions
    too, not just the single-program path.

    Counts across every graph / family / ``include_singleton`` variant
    solved so far, so callers asserting "compiles == #buckets" should clear
    the caches first. Returns -1 if the (private) jit cache probe
    disappears in a future JAX.
    """
    total = 0
    for fn in (_solve_bucket, _solve_bucket_sharded):
        probe = getattr(fn, "_cache_size", None)
        if not callable(probe):
            return -1
        total += int(probe())
    return total


def clear_bucket_solver_caches() -> None:
    """Reset the bucket-solver compile caches — fit AND proximal, plain
    and mesh-sharded — so :func:`bucket_compile_count` and
    :func:`prox_compile_count` restart from zero — what tests and
    benches asserting the absolute "compiles == #buckets" invariant call
    first."""
    _solve_bucket.clear_cache()
    _solve_bucket_sharded.clear_cache()
    _solve_bucket_prox.clear_cache()
    _solve_bucket_prox_sharded.clear_cache()


def _bucket_weights(sample_weight, nodes: np.ndarray, n: int):
    """Per-bucket (k, n) weight rows from a global (n,) or per-node (p, n)
    sample-weight array; ``None`` means unweighted."""
    if sample_weight is None:
        return None
    sample_weight = jnp.asarray(sample_weight)
    if sample_weight.ndim == 1:
        return jnp.broadcast_to(sample_weight[None, :], (len(nodes), n))
    return sample_weight[jnp.asarray(nodes)]


def _bucket_warm_start(warm_start, b: DegreeBucket, dC: int, lead: int,
                       C: int, dtype) -> jnp.ndarray:
    """Stack per-node warm-start thetas into the bucket's padded (k, d*C)."""
    W0 = np.zeros((len(b.nodes), dC), dtype=np.float32)
    if warm_start is not None:
        degs = b.mask.sum(axis=1).astype(np.int64)
        for row, i in enumerate(b.nodes):
            w = warm_start[int(i)]
            if w is None:
                continue
            di = (lead + int(degs[row])) * C
            W0[row, :di] = np.asarray(w, dtype=np.float32)[:di]
    return jnp.asarray(W0, dtype=dtype)


def fit_all_local_batched(graph: Graph, X: jnp.ndarray,
                          include_singleton: bool = True,
                          theta_fixed: Optional[jnp.ndarray] = None,
                          n_iter: int = 40,
                          sample_weight: Optional[jnp.ndarray] = None,
                          warm_start: Optional[Sequence] = None,
                          family=None, mesh=None,
                          want_influence: bool = True,
                          recorder=None,
                          stats: Optional[dict] = None) -> List[LocalFit]:
    """Fit all p local CL estimators via degree-bucketed batched solves.

    Drop-in replacement for the per-node loop: returns the same
    ``List[LocalFit]`` (ordered by node), with per-node results trimmed back
    to the node's true block count. ``family`` selects the model family
    (default Ising); local parameter vectors follow
    ``family.beta(graph, i, include_singleton)`` block order.

    Streaming extensions:
      sample_weight — ``(n,)`` shared or ``(p, n)`` per-node 0/1 observation
        masks over the sample pool; rows with weight 0 are invisible to the
        fit (so a zero-padded, capacity-doubling buffer compiles once per
        capacity, not once per sample count). Weights are meant to be masks;
        the sandwich J uses the masked scores directly.
      warm_start — optional length-p sequence of previous per-node thetas
        (``None`` entries allowed) used to seed Newton; incremental re-fits
        then converge in a couple of damped steps.

    Scale-out: ``mesh`` (a :func:`jax.make_mesh` mesh with a ``data`` axis,
    e.g. from :mod:`repro.launch.mesh`) runs every bucket solve through
    :func:`_solve_bucket_sharded` — bucket nodes sharded along the ``data``
    axis, sample pool replicated. On a one-device mesh the sharded path is
    numerically identical to the default path; ``mesh=None`` keeps the
    plain single-program solve.

    ``want_influence=False`` skips materializing the (n, d) per-sample
    influence stacks (``LocalFit.s`` comes back with zero rows) — only the
    Linear-Opt combiner reads them, and a compiled estimation session whose
    requested combiners never declare ``"influence"`` opts out.

    Observability: ``recorder`` (a :mod:`repro.telemetry` recorder; the
    allocation-free ``NULL_RECORDER`` when None) gets one ``bucket_solve``
    span per degree bucket with Newton-iteration histograms and, on a
    mesh, one ``engine.shard_rows`` observation per device (the bucket
    rows that device solved, tagged ``deg_pad`` and ``device``); ``stats``
    (a caller-provided dict) receives the compile-time split —
    ``stats["compile_s"]`` accumulates the wall seconds of bucket
    dispatches that triggered a compilation (the first-dispatch path) and
    ``stats["dispatch_s"]`` the total dispatch wall. Both default to off
    and cost nothing when unused.
    """
    if family is None:
        family = ISING
    rec = NULL_RECORDER if recorder is None else recorder
    track = stats is not None or rec.enabled
    C = family.block_dim
    if theta_fixed is None:
        theta_fixed = jnp.zeros(family.n_params(graph), X.dtype)
    theta_fixed = jnp.asarray(theta_fixed)
    node_tf = theta_fixed[: graph.p * C].reshape(graph.p, C)
    n = X.shape[0]
    lead = 1 if include_singleton else 0

    out: List[Optional[LocalFit]] = [None] * graph.p
    for b in degree_buckets(graph):
        k = len(b.nodes)
        offsets = node_tf[jnp.asarray(b.nodes)]
        dC = (b.deg_pad + lead) * C
        sw = _bucket_weights(sample_weight, b.nodes, n)
        W0 = _bucket_warm_start(warm_start, b, dC, lead, C,
                                _solver_dtype(X.dtype))
        weighted = sample_weight is not None
        if sw is None:
            sw = jnp.ones((1, 1), _solver_dtype(X.dtype))  # never read
        if track:
            c0 = bucket_compile_count()
            t0 = time.perf_counter()
        span = (rec.span("bucket_solve", deg_pad=b.deg_pad, k=k)
                if rec.enabled else None)
        if span is not None:
            span.__enter__()
        if mesh is None:
            W, H, J, V, S, I = _solve_bucket(
                X, jnp.asarray(b.nodes), jnp.asarray(b.nbrs),
                jnp.asarray(b.mask), offsets, W0, sw, include_singleton,
                n_iter, weighted, warm_start is not None, family,
                want_influence=want_influence)
        else:
            shards = _mesh_data_size(mesh)
            nodes_, nbrs_, mask_, offsets_, W0_ = _pad_bucket_rows(
                shards, jnp.asarray(b.nodes), jnp.asarray(b.nbrs),
                jnp.asarray(b.mask), offsets, W0)
            sw_ = _pad_bucket_rows(shards, sw)[0] if weighted else sw
            W, H, J, V, S, I = _solve_bucket_sharded(
                X, nodes_, nbrs_, mask_, offsets_, W0_, sw_,
                include_singleton, n_iter, weighted,
                warm_start is not None, family, mesh,
                want_influence=want_influence)
            if rec.enabled:
                # bucket rows (padding included) each device solved
                for s in W.addressable_shards:
                    rec.observe("engine.shard_rows", int(s.data.shape[0]),
                                deg_pad=b.deg_pad, device=int(s.device.id))
        W, H, J, V, S = (np.asarray(W)[:k], np.asarray(H)[:k],
                         np.asarray(J)[:k], np.asarray(V)[:k],
                         np.asarray(S)[:k])
        if span is not None:
            span.__exit__(None, None, None)
        if track:
            # the np.asarray conversions above block on the device work, so
            # dt covers trace+compile+execute for a compiling dispatch
            dt = time.perf_counter() - t0
            c1 = bucket_compile_count()
            compiled = c1 > c0 >= 0
            if stats is not None:
                stats["dispatch_s"] = stats.get("dispatch_s", 0.0) + dt
                if compiled:
                    stats["compile_s"] = stats.get("compile_s", 0.0) + dt
            if rec.enabled:
                rec.observe("engine.newton_iters", int(np.max(np.asarray(I)[:k])),
                            deg_pad=b.deg_pad)
                rec.observe("engine.bucket_dispatch_s", dt,
                            deg_pad=b.deg_pad, compiled=compiled)
        degs = b.mask.sum(axis=1).astype(np.int64)
        for row, i in enumerate(b.nodes):
            i = int(i)
            di = (lead + int(degs[row])) * C
            out[i] = LocalFit(
                i=i, beta=family.beta(graph, i, include_singleton),
                theta=W[row, :di].copy(), H=H[row, :di, :di].copy(),
                J=J[row, :di, :di].copy(), V=V[row, :di, :di].copy(),
                s=S[row, :, :di].copy())
    return out  # type: ignore[return-value]


# ------------------------------------------------------- proximal updates
@_float32_contractions
def _solve_bucket_prox_impl(X, nodes, nbrs, mask, offsets, W0, sw, lam, rho,
                            tbar, include_singleton: bool, n_iter: int,
                            weighted: bool = False, family=ISING,
                            tol: float = 2e-6, ridge: float = 1e-8,
                            max_step: float = 5.0,
                            axis_name: Optional[str] = None):
    """ADMM primal update for a whole degree bucket in one XLA program.

    Maximizes, per node,  ``l^i(w) - lam'w - sum_a rho_a (w_a - tbar_a)^2/2``
    (the objective of :func:`repro.core.admm._prox_solve`) with the same
    closed-form family-dispatched Newton machinery as :func:`_solve_bucket`:
    the prox terms only shift the gradient by ``-lam - rho*(w - tbar)`` and
    the Hessian by ``-diag(rho)``, so the bucket stays uniformly negative
    definite. lam, rho, tbar: (k, d*C) with zeros on padded coordinates.
    ``axis_name`` as in :func:`_solve_bucket_impl`. Returns W only.
    """
    n = X.shape[0]
    Zb, xi, base, cmask = _bucket_design(family, X, nodes, nbrs, mask,
                                         offsets, include_singleton)
    k, C, d, _ = Zb.shape
    dC = d * C
    cdtype = _solver_dtype(Zb.dtype)
    W0 = W0.astype(cdtype)
    eye = jnp.eye(dC, dtype=cdtype)
    cflat = _flat_coord_mask(cmask, C)
    pad_diag = (1.0 - cflat)[:, :, None] * eye[None, :, :]
    rho_diag = rho[:, :, None] * eye[None, :, :]
    if weighted:
        denom = jnp.maximum(jnp.sum(sw, axis=1), 1.0)
    else:
        denom = jnp.full((k,), float(n), cdtype)

    score_curvature, grad_vec, curvature_matrix, avg_loglik, _, \
        newton_stats = _channel_ops(family, Zb, base, xi, sw, weighted, denom)

    def objective(Ws):
        # (c, k): penalized criterion for a stack of candidate points
        pen = (lam[None] * Ws).sum(axis=2) \
            + 0.5 * (rho[None] * (Ws - tbar[None]) ** 2).sum(axis=2)
        return avg_loglik(Ws) - pen

    def cond(carry):
        _, it, delta = carry
        return (it < n_iter) & (delta > tol)

    def newton_step(carry):
        W, it, _ = carry
        g_raw, K_raw = newton_stats(W)           # fused score + Gram
        g = g_raw / denom[:, None] - lam - rho * (W - tbar)
        H = -K_raw / denom[:, None, None] \
            - rho_diag - ridge * eye[None, :, :] - pad_diag
        dirn = _gauss_jordan_solve(H, g[..., None])[..., 0]
        finite = jnp.all(jnp.isfinite(dirn), axis=1, keepdims=True)
        dirn = jnp.where(finite, dirn, 0.0)
        norm = jnp.linalg.norm(dirn, axis=1, keepdims=True)
        untrusted = (norm > max_step) | ~finite
        dirn = jnp.where(norm > max_step,
                         dirn * (max_step / (norm + 1e-30)), dirn)

        # same saturation guard as _solve_bucket, on the penalized objective
        step = jax.lax.cond(
            jnp.any(untrusted),
            lambda: _backtrack_step(objective, W, dirn, g, max_step),
            lambda: dirn)
        delta = jnp.max(jnp.abs(step))
        if axis_name is not None:
            delta = jax.lax.pmax(delta, axis_name)
        return W - step, it + 1, delta

    W, _, _ = jax.lax.while_loop(cond, newton_step, (W0, 0, jnp.inf))
    return W


@functools.partial(jax.jit,
                   static_argnames=("include_singleton", "n_iter", "weighted",
                                    "family"))
def _solve_bucket_prox(X, nodes, nbrs, mask, offsets, W0, sw, lam, rho, tbar,
                       include_singleton: bool, n_iter: int,
                       weighted: bool = False, family=ISING, tol: float = 2e-6,
                       ridge: float = 1e-8, max_step: float = 5.0):
    """Single-device proximal bucket solve (jitted impl)."""
    return _solve_bucket_prox_impl(X, nodes, nbrs, mask, offsets, W0, sw,
                                   lam, rho, tbar, include_singleton, n_iter,
                                   weighted, family, tol, ridge, max_step)


@functools.partial(jax.jit,
                   static_argnames=("include_singleton", "n_iter", "weighted",
                                    "family", "mesh"))
def _solve_bucket_prox_sharded(X, nodes, nbrs, mask, offsets, W0, sw, lam,
                               rho, tbar, include_singleton: bool,
                               n_iter: int, weighted: bool = False,
                               family=ISING, mesh=None):
    """Mesh-sharded proximal bucket solve — the ADMM-primal twin of
    :func:`_solve_bucket_sharded` (same data-axis node sharding, replicated
    sample pool, one scalar max per Newton iteration)."""
    body = functools.partial(
        _solve_bucket_prox_impl, include_singleton=include_singleton,
        n_iter=n_iter, weighted=weighted, family=family, axis_name="data")
    data = P("data")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), data, data, data, data, data,
                  data if weighted else P(), data, data, data),
        out_specs=data,
        check_vma=False,
    )(X, nodes, nbrs, mask, offsets, W0, sw, lam, rho, tbar)


def prox_compile_count() -> int:
    """Proximal-solver compilations (plain + mesh-sharded) — the ADMM twin
    of :func:`bucket_compile_count`, used for the joint verb's
    compile-time split. Returns -1 if the jit-cache probe is gone."""
    total = 0
    for fn in (_solve_bucket_prox, _solve_bucket_prox_sharded):
        probe = getattr(fn, "_cache_size", None)
        if not callable(probe):
            return -1
        total += int(probe())
    return total


def group_soft_threshold(v: np.ndarray, thr: float, block_dim: int,
                         lead: int = 1) -> np.ndarray:
    """Group soft-thresholding on a ``family.beta``-ordered local vector.

    The proximal operator of ``thr * sum_blocks ||w_block||_2`` in the
    coordinate-major per-node layout the bucket solvers emit: the first
    ``lead`` blocks (the unpenalized singleton block, when free) pass
    through untouched; every following ``block_dim``-wide edge block ``g``
    is scaled by ``max(0, 1 - thr / ||g||_2)`` — shrunk toward zero and
    EXACTLY zeroed once its norm falls below ``thr``, which is what lets
    structure learning read the support off the iterate with no epsilon
    tolerance. At C = 1 this is the scalar soft-threshold, so plain-lasso
    Ising/Gaussian selection and group-lasso Potts selection share one
    code path (the z-update half of the ADMM split whose smooth half is
    :func:`prox_update_batched`).
    """
    v = np.asarray(v, dtype=np.float64)
    off = lead * block_dim
    nblk, rem = divmod(v.size - off, block_dim)
    if rem:
        raise ValueError(
            f"vector of length {v.size} is not lead={lead} plus whole "
            f"blocks of size {block_dim}")
    out = v.copy()
    if nblk > 0 and thr > 0.0:
        blocks = out[off:].reshape(nblk, block_dim)
        norms = np.linalg.norm(blocks, axis=1)
        scale = np.where(norms > thr,
                         1.0 - thr / np.where(norms > 0.0, norms, 1.0), 0.0)
        out[off:] = (blocks * scale[:, None]).ravel()
    return out


def prox_update_batched(graph: Graph, X: jnp.ndarray,
                        theta_bar: np.ndarray,
                        lambdas: Sequence[np.ndarray],
                        rhos: Sequence[np.ndarray],
                        thetas0: Optional[Sequence[np.ndarray]] = None,
                        include_singleton: bool = True,
                        theta_fixed: Optional[jnp.ndarray] = None,
                        sample_weight: Optional[jnp.ndarray] = None,
                        n_iter: int = 15, family=None,
                        mesh=None, recorder=None,
                        stats: Optional[dict] = None) -> List[np.ndarray]:
    """Batched ADMM primal update across all nodes (one solve per bucket).

    Per-node inputs follow :func:`repro.core.admm.admm_mple`: ``lambdas`` /
    ``rhos`` are length-p lists of ``beta_i``-length vectors, ``theta_bar``
    is the full flat consensus iterate — or, for asynchronous streaming
    where every node holds its own possibly-stale consensus view, a
    length-p list of ``beta_i``-length vectors. ``thetas0`` are optional
    warm starts (defaults to the consensus view restricted to ``beta_i``).
    Supports the same ``sample_weight`` masks as
    :func:`fit_all_local_batched`, which is what lets the streaming engine
    run ADMM rounds over a growing buffer without recompiling, the same
    ``family`` dispatch (default Ising; ``beta_i`` then follows
    ``family.beta`` block order), and the same ``mesh`` scale-out path
    (bucket nodes sharded along the mesh's ``data`` axis). Returns the
    updated per-node theta vectors.

    ``recorder`` / ``stats`` mirror :func:`fit_all_local_batched`: one
    ``prox_bucket_solve`` span per bucket, and ``stats["compile_s"]`` /
    ``stats["dispatch_s"]`` accumulation keyed to the prox-solver caches.
    """
    if family is None:
        family = ISING
    rec = NULL_RECORDER if recorder is None else recorder
    track = stats is not None or rec.enabled
    C = family.block_dim
    if theta_fixed is None:
        theta_fixed = jnp.zeros(family.n_params(graph), X.dtype)
    theta_fixed = jnp.asarray(theta_fixed)
    node_tf = theta_fixed[: graph.p * C].reshape(graph.p, C)
    per_node_bar = isinstance(theta_bar, (list, tuple))
    if not per_node_bar:
        theta_bar = np.asarray(theta_bar)
    n = X.shape[0]
    lead = 1 if include_singleton else 0

    out: List[Optional[np.ndarray]] = [None] * graph.p
    for b in degree_buckets(graph):
        k = len(b.nodes)
        dC = (b.deg_pad + lead) * C
        degs = b.mask.sum(axis=1).astype(np.int64)
        lam = np.zeros((k, dC), dtype=np.float32)
        rho = np.zeros((k, dC), dtype=np.float32)
        tbar = np.zeros((k, dC), dtype=np.float32)
        for row, i in enumerate(b.nodes):
            i = int(i)
            di = (lead + int(degs[row])) * C
            lam[row, :di] = np.asarray(lambdas[i])[:di]
            rho[row, :di] = np.asarray(rhos[i])[:di]
            if per_node_bar:
                tbar[row, :di] = np.asarray(theta_bar[i])[:di]
            else:
                beta = np.asarray(family.beta(graph, i, include_singleton))
                tbar[row, :di] = theta_bar[beta][:di]
        # warm-start at the previous iterate where given; nodes without one
        # (thetas0 absent or a None entry) start at their consensus view
        W0 = np.array(tbar, copy=True)
        if thetas0 is not None:
            for row, i in enumerate(b.nodes):
                t0 = thetas0[int(i)]
                if t0 is not None:
                    di = (lead + int(degs[row])) * C
                    W0[row, :di] = np.asarray(t0, dtype=np.float32)[:di]
        W0 = jnp.asarray(W0, dtype=_solver_dtype(X.dtype))
        sw = _bucket_weights(sample_weight, b.nodes, n)
        weighted = sample_weight is not None
        if sw is None:
            sw = jnp.ones((1, 1), _solver_dtype(X.dtype))
        offsets = node_tf[jnp.asarray(b.nodes)]
        if track:
            c0 = prox_compile_count()
            t0 = time.perf_counter()
        span = (rec.span("prox_bucket_solve", deg_pad=b.deg_pad, k=k)
                if rec.enabled else None)
        if span is not None:
            span.__enter__()
        if mesh is None:
            W = _solve_bucket_prox(
                X, jnp.asarray(b.nodes), jnp.asarray(b.nbrs),
                jnp.asarray(b.mask), offsets, W0, sw,
                jnp.asarray(lam), jnp.asarray(rho), jnp.asarray(tbar),
                include_singleton, n_iter, weighted, family)
        else:
            shards = _mesh_data_size(mesh)
            nodes_, nbrs_, mask_, offsets_, W0_, lam_, rho_, tbar_ = \
                _pad_bucket_rows(shards, jnp.asarray(b.nodes),
                                 jnp.asarray(b.nbrs), jnp.asarray(b.mask),
                                 offsets, W0, jnp.asarray(lam),
                                 jnp.asarray(rho), jnp.asarray(tbar))
            sw_ = _pad_bucket_rows(shards, sw)[0] if weighted else sw
            W = _solve_bucket_prox_sharded(
                X, nodes_, nbrs_, mask_, offsets_, W0_, sw_, lam_, rho_,
                tbar_, include_singleton, n_iter, weighted, family, mesh)
        W = np.asarray(W)[:len(b.nodes)]
        if span is not None:
            span.__exit__(None, None, None)
        if track:
            dt = time.perf_counter() - t0
            c1 = prox_compile_count()
            compiled = c1 > c0 >= 0
            if stats is not None:
                stats["dispatch_s"] = stats.get("dispatch_s", 0.0) + dt
                if compiled:
                    stats["compile_s"] = stats.get("compile_s", 0.0) + dt
            if rec.enabled:
                rec.observe("engine.prox_dispatch_s", dt,
                            deg_pad=b.deg_pad, compiled=compiled)
        for row, i in enumerate(b.nodes):
            di = (lead + int(degs[row])) * C
            out[int(i)] = W[row, :di].copy()
    return out  # type: ignore[return-value]
