"""The plain reference: pseudo-likelihood estimation in float64 numpy.

It imports nothing of the program and follows the method as Liu & Ihler
(2012) state it, with the program's documented numerical conventions:

* node i's local estimator maximises the average conditional
  log-likelihood of x_i given its neighbours over beta_i = [theta_i,
  theta_ij for j in N(i) in edge order] (Eq. 3), solved by Newton's method
  to convergence;
* the sandwich variance V = H^-1 J H^-1 with H the average curvature
  (+1e-9 ridge) and J the average outer product of per-sample scores, and
  the per-sample influence s = H^-1 g_n;
* the one-step combiners (Sec. 3.1): uniform, diagonal (1 / V_aa),
  optimal (weights V_a^-1 1 from the influence cross-covariance, diagonal
  where degenerate), max (the owner of least V_aa) and the weighted
  median of the owners' estimates with mass 1 / V_aa; an owner whose
  estimate is non-finite or beyond 25 in magnitude does not vote;
* ADMM joint MPLE (Sec. 3.2) initialised at the diagonal consensus with
  penalties 1 / V_aa;
* the gradient of the average pseudo-likelihood, whose norm the program
  reports as ``score_norm``.

Families: Ising (x in {-1, +1}, p(x_i | rest) = sigmoid(2 x_i eta_i)) and
the unit-variance Gaussian MRF (x_i | rest ~ N(eta_i, 1)).

``prec`` selects the arithmetic: ``"f64"`` is the reference; ``"high"``
and ``"bf16"`` round every contraction operand to 16 or 8 mantissa bits
and accumulate in float32 (the TPU's three-pass and one-pass float32
matmuls), with all else in float32. Those two are the controls that
decide how tight a comparison must be.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

TRUST_RADIUS = 25.0
_RIDGE_SANDWICH = 1e-9
_RIDGE_NEWTON = 1e-8


# ------------------------------------------------------------ arithmetic
class Arith:
    """Float64, or float32 with contraction operands rounded."""

    def __init__(self, prec: str = "f64") -> None:
        if prec not in ("f64", "high", "bf16"):
            raise ValueError(f"unknown reference precision {prec!r}")
        self.prec = prec
        self.dtype = np.float64 if prec == "f64" else np.float32
        self._drop = {"f64": 0, "high": 7, "bf16": 16}[prec]

    def op(self, a) -> np.ndarray:
        """An operand as the contraction unit sees it."""
        a = np.ascontiguousarray(a, self.dtype)
        if not self._drop:
            return a
        # round to nearest even, keeping 23 - drop mantissa bits
        b = a.view(np.uint32).astype(np.uint64)
        half = np.uint64(1 << (self._drop - 1))
        low = np.uint64((1 << self._drop) - 1)
        odd = (b >> np.uint64(self._drop)) & np.uint64(1)
        b = (b + half - np.uint64(1) + odd) & ~low
        return b.astype(np.uint32).view(np.float32)

    def ew(self, a) -> np.ndarray:
        return np.asarray(a, self.dtype)


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def _log_sigmoid(z):
    return -np.logaddexp(0.0, -z)


class IsingRef:
    name = "ising"

    @staticmethod
    def loglik(eta, x):
        return _log_sigmoid(2.0 * x * eta)

    @staticmethod
    def score(eta, x):
        return 2.0 * x * _sigmoid(-2.0 * x * eta)

    @staticmethod
    def curvature(eta, x):
        s = _sigmoid(2.0 * eta)
        return 4.0 * s * (1.0 - s)


class GaussianRef:
    name = "gaussian"

    @staticmethod
    def loglik(eta, x):
        return -0.5 * (x - eta) ** 2

    @staticmethod
    def score(eta, x):
        return x - eta

    @staticmethod
    def curvature(eta, x):
        return np.ones_like(eta)


FAMILIES = {"ising": IsingRef, "gaussian": GaussianRef}


# ------------------------------------------------------------ structure
def neighbors(p: int, edges: Sequence) -> List[np.ndarray]:
    nb: List[list] = [[] for _ in range(p)]
    for i, j in edges:
        nb[i].append(j)
        nb[j].append(i)
    return [np.asarray(sorted(v), dtype=np.int64) for v in nb]


def incident(p: int, edges: Sequence) -> List[np.ndarray]:
    """Edge indices touching each node, in edge order (== ascending
    neighbour order for sorted edges)."""
    inc: List[list] = [[] for _ in range(p)]
    for k, (i, j) in enumerate(edges):
        inc[i].append(k)
        inc[j].append(k)
    return [np.asarray(v, dtype=np.int64) for v in inc]


# ------------------------------------------------------------ local fits
class Designs:
    """Every node's local design, built once: blocks of nodes of equal
    degree with their (k, d, n) designs [1, x_j for j in N(i)] and (k, n)
    targets x_i, at the arithmetic ``prec``."""

    def __init__(self, X, p: int, edges, nodes=None, prec: str = "f64"):
        self.A = A = Arith(prec)
        X = np.asarray(X)
        self.n = n = X.shape[0]
        nb = neighbors(p, edges)
        by_deg: Dict[int, list] = {}
        for i in (range(p) if nodes is None else nodes):
            by_deg.setdefault(len(nb[int(i)]), []).append(int(i))
        self.blocks = []
        for deg, group in sorted(by_deg.items()):
            # blocks of nodes so a (k, d, n) design stays a few hundred MB
            step = max(1, int(2.5e7 // (n * (deg + 1))))
            for lo in range(0, len(group), step):
                idx = group[lo:lo + step]
                Z = np.ones((len(idx), deg + 1, n), A.dtype)
                for r, i in enumerate(idx):
                    Z[r, 1:] = X[:, nb[i]].T
                self.blocks.append((idx, A.op(Z), A.ew(X[:, idx].T)))


def local_fits(family: str, X, p: int, edges, nodes=None, *,
               want_influence: bool = False, prec: str = "f64",
               designs: Optional[Designs] = None,
               tol: float = 1e-12, max_iter: int = 100,
               max_step: float = 5.0, penalty=None) -> Dict[int, dict]:
    """Local CL estimates of ``nodes`` (default: all) -> {i: {"theta",
    "vdiag"[, "s"]}}.

    ``penalty``, for ADMM, maps node i to (lam, rho, tbar, w0): the
    objective gains -lam'w - sum rho (w - tbar)^2 / 2 and Newton starts at
    w0; the sandwich is then skipped. ``designs`` reuses designs built
    before (for the same X, nodes and ``prec``).
    """
    fam = FAMILIES[family]
    D = designs if designs is not None else Designs(X, p, edges, nodes,
                                                    prec)
    if D.A.dtype != np.float64:
        tol = max(tol, 2e-6)           # float32 round-off floor
    out: Dict[int, dict] = {}
    for idx, Zo, xi in D.blocks:
        out.update(_fit_block(fam, D.A, idx, Zo, xi, want_influence, tol,
                              max_iter, max_step, penalty))
    return out


def _fit_block(fam, A, idx, Zo, xi, want_influence, tol, max_iter,
               max_step, penalty):
    k, d, n = Zo.shape
    if penalty is None:
        W = np.zeros((k, d), A.dtype)
        lam = rho = tbar = np.zeros((k, d), A.dtype)
    else:
        lam = A.ew(np.stack([penalty[i][0] for i in idx]))
        rho = A.ew(np.stack([penalty[i][1] for i in idx]))
        tbar = A.ew(np.stack([penalty[i][2] for i in idx]))
        W = A.ew(np.stack([penalty[i][3] for i in idx]))
    eye = np.eye(d, dtype=A.dtype)

    def stats(W):
        eta = A.ew(np.einsum("kdn,kd->kn", Zo, A.op(W)))
        r = A.ew(fam.score(eta, xi))
        kap = A.ew(fam.curvature(eta, xi))
        g = A.ew(np.einsum("kdn,kn->kd", Zo, A.op(r))) / n
        H = A.ew(Zo @ A.op(Zo * kap[:, None, :]).transpose(0, 2, 1)) / n
        return r, g, H

    for _ in range(max_iter):
        _, g, H = stats(W)
        g = g - lam - rho * (W - tbar)
        # the program's 1e-8 ridge on the Newton system (direction only)
        H = H + rho[:, :, None] * eye + _RIDGE_NEWTON * eye
        try:
            step = np.linalg.solve(H, g[..., None])[..., 0]
        except np.linalg.LinAlgError:
            step = (np.linalg.pinv(H) @ g[..., None])[..., 0]
        norm = np.linalg.norm(step, axis=1, keepdims=True)
        step = step * np.minimum(1.0, max_step / np.maximum(norm, 1e-300))
        W = A.ew(W + step)
        if np.max(np.abs(step)) <= tol:
            break
    out = {}
    if penalty is not None:
        for r, i in enumerate(idx):
            out[i] = {"theta": W[r].astype(np.float64)}
        return out
    r, _, H = stats(W)
    Go = A.op(Zo * A.op(r)[:, None, :])                      # (k, d, n)
    J = A.ew(Go @ Go.transpose(0, 2, 1)) / n
    try:
        Hinv = np.linalg.inv(H + _RIDGE_SANDWICH * eye)
    except np.linalg.LinAlgError:
        # a saturated fit in float32 (curvature rounded to 0): no inverse
        Hinv = np.linalg.pinv(H + _RIDGE_SANDWICH * eye)
    V = Hinv @ J @ Hinv
    S = (Go.transpose(0, 2, 1) @ A.op(Hinv).transpose(0, 2, 1)
         if want_influence else None)
    for r_, i in enumerate(idx):
        out[i] = {"theta": W[r_].astype(np.float64),
                  "vdiag": np.diag(V[r_]).astype(np.float64)}
        if S is not None:
            out[i]["s"] = S[r_].astype(np.float64)
    return out


# ------------------------------------------------------------ combiners
def owners(p: int, edges) -> List[List[tuple]]:
    """Owners [(node, position in its beta)] of every flat parameter."""
    own: List[List[tuple]] = [[(i, 0)] for i in range(p)]
    own += [[] for _ in edges]
    for i, inc in enumerate(incident(p, edges)):
        for pos, k in enumerate(inc):
            own[p + int(k)].append((i, 1 + pos))
    return own


def combine(scheme: str, fits: Dict[int, dict], p: int, edges,
            params=None) -> Dict[int, object]:
    """Combined value of each parameter in ``params`` (default: all).

    Returns {a: value} for the linear schemes and {a: [admissible values]}
    for "max" and "weighted_vote": where the owners' vote masses tie to
    within 1e-4 of each other, float32 cannot decide the vote, and either
    owner's estimate is a correct answer.
    """
    own = owners(p, edges)
    params = range(len(own)) if params is None else params
    out = {}
    for a in params:
        ow = own[a]
        est = np.array([fits[i]["theta"][pos] for i, pos in ow])
        var = np.maximum(np.array([fits[i]["vdiag"][pos] for i, pos in ow]),
                         1e-12)
        bad = ~np.isfinite(est) | ~np.isfinite(var) \
            | (np.abs(est) > TRUST_RADIUS)
        est = np.where(bad, 0.0, est)
        if bad.all():
            out[a] = [0.0] if scheme in ("max", "weighted_vote") else 0.0
            continue
        if len(ow) == 1:
            out[a] = [est[0]] if scheme in ("max", "weighted_vote") \
                else est[0]
            continue
        mass = np.where(bad, 0.0, 1.0 / np.where(bad, 1.0, var))
        if scheme == "uniform":
            w = np.where(bad, 0.0, 1.0)
        elif scheme == "diagonal":
            w = mass
        elif scheme == "optimal":
            cols = np.stack([fits[i]["s"][:, pos] for i, pos in ow])
            Va = cols @ cols.T / cols.shape[1]
            w = np.linalg.solve(Va + 1e-10 * np.eye(len(ow)),
                                np.ones(len(ow)))
            if bad.any() or not np.isfinite(Va).all() \
                    or abs(w.sum()) < 1e-12:
                w = mass
        elif scheme in ("max", "weighted_vote"):
            out[a] = _vote(scheme, est, mass)
            continue
        else:
            raise ValueError(f"no reference for combiner {scheme!r}")
        out[a] = float((w * est).sum() / w.sum())
    return out


def _vote(scheme, est, mass, tie=1e-4):
    """Admissible winners of a vote: each owner whose win survives a
    relative change of ``tie`` in the masses."""
    wins = set()
    k = len(est)
    for j in range(k):
        for sgn in (-1.0, 0.0, 1.0):
            m = mass.copy()
            m[j] *= 1.0 + sgn * tie
            if scheme == "max":
                wins.add(int(np.argmax(m)))
            else:
                order = np.argsort(est, kind="stable")
                cum = np.cumsum(m[order])
                wins.add(int(order[int(np.argmax(cum >= 0.5 * cum[-1]))]))
    return [float(est[j]) for j in sorted(wins)]


def combined_gaps(got, ref: Dict[int, object]) -> np.ndarray:
    """|got[a] - ref[a]| for each parameter a of ``ref`` (the nearest
    admissible value for votes)."""
    out = []
    for a, v in ref.items():
        vals = v if isinstance(v, list) else [v]
        out.append(min(abs(float(got[a]) - u) for u in vals))
    return np.asarray(out)


# ------------------------------------------------------------ score norm
def pseudo_score(family: str, theta, X, p: int, edges,
                 prec: str = "f64") -> np.ndarray:
    """Gradient of the average pseudo-log-likelihood at flat ``theta``."""
    fam = FAMILIES[family]
    A = Arith(prec)
    XT = A.op(np.asarray(X).T)                               # (p, n)
    theta = A.op(theta)
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    inc = incident(p, edges)
    etaT = np.repeat(theta[:p, None], XT.shape[1], axis=1)
    # eta_i = theta_i + sum_j theta_ij x_j, one neighbour slot at a time
    for slot in range(max((len(v) for v in inc), default=0)):
        rows = np.asarray([i for i in range(p) if len(inc[i]) > slot])
        ks = np.asarray([inc[i][slot] for i in rows])
        other = np.where(e[ks, 0] == rows, e[ks, 1], e[ks, 0])
        etaT[rows] += XT[other] * theta[p + ks][:, None]
    rT = A.op(fam.score(etaT, XT))
    n = XT.shape[1]
    g_node = rT.sum(axis=1) / n
    g_edge = (np.einsum("in,in->i", rT[e[:, 0]], XT[e[:, 1]])
              + np.einsum("in,in->i", rT[e[:, 1]], XT[e[:, 0]])) / n
    return np.concatenate([g_node, g_edge]).astype(np.float64)


# ------------------------------------------------------------ ADMM
def admm_joint(family: str, X, p: int, edges, n_iters: int,
               prec: str = "f64") -> np.ndarray:
    """Final consensus of ADMM joint MPLE from the diagonal one-step
    start, penalties 1 / V_aa, ``n_iters`` rounds."""
    designs = Designs(X, p, edges, prec=prec)
    fits = local_fits(family, X, p, edges, designs=designs)
    own = owners(p, edges)
    bar = np.array([v for _, v in sorted(
        combine("diagonal", fits, p, edges).items())])
    betas = [np.asarray([i] + [p + int(k) for k in inc])
             for i, inc in enumerate(incident(p, edges))]
    rhos = [1.0 / np.maximum(fits[i]["vdiag"], 1e-12) for i in range(p)]
    lams = [np.zeros(len(b)) for b in betas]
    thetas = [bar[b].copy() for b in betas]
    for _ in range(n_iters):
        pen = {i: (lams[i], rhos[i], bar[betas[i]], thetas[i])
               for i in range(p)}
        sol = local_fits(family, X, p, edges, designs=designs, penalty=pen)
        thetas = [sol[i]["theta"] for i in range(p)]
        new = bar.copy()
        for a, ow in enumerate(own):
            num = sum(rhos[i][pos] * thetas[i][pos] for i, pos in ow)
            den = sum(rhos[i][pos] for i, pos in ow)
            new[a] = num / den
        bar = new
        for i in range(p):
            lams[i] = lams[i] + rhos[i] * (thetas[i] - bar[betas[i]])
    return bar


def local_gaps(got_thetas: Dict[int, np.ndarray], ref: Dict[int, dict],
               nodes: Optional[Sequence[int]] = None) -> np.ndarray:
    """|got - ref| of every coordinate of the local estimates of
    ``nodes`` (default: every node of ``ref``)."""
    nodes = ref.keys() if nodes is None else nodes
    return np.concatenate([
        np.abs(np.asarray(got_thetas[i], np.float64) - ref[i]["theta"])
        for i in nodes])


def gap_numbers(name: str, gaps: np.ndarray) -> Dict[str, float]:
    """``<name>_gap``: the widest gap; ``<name>_rms``: the root mean
    square gap, steadier from seed to seed. Non-finite reads as inf."""
    gaps = np.asarray(gaps, np.float64)
    if gaps.size == 0 or not np.all(np.isfinite(gaps)):
        return {f"{name}_gap": np.inf, f"{name}_rms": np.inf}
    return {f"{name}_gap": float(gaps.max()),
            f"{name}_rms": float(np.sqrt(np.mean(gaps ** 2)))}
