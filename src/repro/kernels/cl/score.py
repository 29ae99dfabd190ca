"""Seed-compatible fused score entry points over the channelized skeleton.

:func:`cl_score` keeps the original single-channel ``(n, p)`` signature the
Ising/Gaussian callers (and the seed tests) use; it is the C = 1 instance
of :func:`repro.kernels.cl.kernel.cl_score_channels`. Multi-channel kinds
(Potts) are rejected here with a pointer to the channelized entry —
:func:`repro.kernels.cl.family.family_score_stats` builds the channelized
inputs from a :class:`ModelFamily` directly.

``cl_score_padded`` / ``cl_score_channels_padded`` are the streaming-buffer
variants: zero-padded rows beyond ``n_seen`` are invisible to the score
Gram for every registered kind (padded feature rows are zero — for Potts
because state 0 is the reference state with an all-zero indicator row), so
only the Gram normalizer needs rescaling from buffer capacity to the live
sample count. Keeping the buffer shape fixed between capacity doublings
means a growing stream re-uses one compiled kernel per capacity.
"""
from __future__ import annotations

from typing import Optional

from .epilogues import registered_kinds, require_epilogue
from .kernel import cl_score_channels


#: families with a registered fused-kernel epilogue (seed-compatible name —
#: the seed tuple ("ising", "gaussian") grew a "potts" entry when the
#: multi-channel epilogue landed). NOTE: an import-time snapshot for
#: seed compatibility only — epilogues registered later won't appear here;
#: live checks must use ``registered_kinds()`` / ``get_epilogue()``.
KERNEL_KINDS = registered_kinds()


def cl_score(x, theta, mask, bias, *, kind: str = "ising",
             interpret: Optional[bool] = None, tiles=None):
    """(eta, r, S) = fused single-channel score statistics.

    x: (n, p); theta, mask: (p, p); bias: (p,). ``kind`` picks the family
    epilogue (one compiled kernel per kind); multi-channel kinds raise —
    use :func:`cl_score_channels` / ``family_score_stats`` for those.
    Returns eta, r of shape (n, p) in x.dtype and S of shape (p, p) in
    float32. ``interpret=None`` derives from the backend (compiled on
    TPU, interpret mode — Python-speed validation — elsewhere);
    ``tiles`` is an optional autotuner :class:`TileConfig`.
    """
    ep = require_epilogue(kind)
    if ep.channels != "single":
        raise ValueError(
            f"kind {kind!r} is multi-channel (C > 1); use cl_score_channels "
            f"with (C, n, p) inputs — see repro.kernels.cl.family")
    eta, r, S = cl_score_channels(x[None], theta[None], mask, bias[None],
                                  kind=kind, interpret=interpret,
                                  tiles=tiles)
    return eta[0], r[0], S[0, 0]


def ising_cl_score(x, theta, mask, bias, *,
                   interpret: Optional[bool] = None):
    """Ising instance of :func:`cl_score` (seed-compatible entry point)."""
    return cl_score(x, theta, mask, bias, kind="ising", interpret=interpret)


def cl_score_padded(x_pad, theta, mask, bias, n_seen: int, *,
                    kind: str = "ising",
                    interpret: Optional[bool] = None):
    """Fused score statistics over a zero-padded streaming buffer.

    ``x_pad`` is a capacity-doubling sample buffer whose rows past ``n_seen``
    are all-zero padding. Zero rows contribute nothing to the score Gram
    (``S = r^T X`` and the padded X rows are zero), so the only correction
    needed is the Gram normalizer: the kernel divides by the buffer
    capacity, we rescale to the live sample count.

    Returns (eta, r, S) like :func:`cl_score`, with ``S`` normalized by
    ``n_seen``. For the Ising kind, rows of ``r`` past ``n_seen`` are
    guaranteed zero (``x = 0`` makes ``r = 2 x sigma(-2 x eta) = 0``); the
    Gaussian residual ``x - eta`` is ``-bias`` on padded rows, so consumers
    of per-sample residuals must slice ``r[:n_seen]`` (the singleton
    gradient assembly in :func:`repro.stream.online.pseudo_score` does).
    """
    eta, r, S = cl_score(x_pad, theta, mask, bias, kind=kind,
                         interpret=interpret)
    scale = x_pad.shape[0] / max(int(n_seen), 1)
    return eta, r, S * scale


def ising_cl_score_padded(x_pad, theta, mask, bias, n_seen: int, *,
                          interpret: Optional[bool] = None):
    """Ising instance of :func:`cl_score_padded` (seed-compatible name)."""
    return cl_score_padded(x_pad, theta, mask, bias, n_seen, kind="ising",
                           interpret=interpret)


def cl_score_channels_padded(F_pad, theta, mask, bias, n_seen: int, *,
                             kind: str, interpret: Optional[bool] = None):
    """Channelized :func:`cl_score_padded`: F_pad is (C, capacity, p) with
    all-zero feature rows past ``n_seen`` (for Potts, zero-padded raw rows
    ARE the all-zero reference-state indicator rows). S is renormalized to
    the live count; per-sample consumers must slice ``r[:, :n_seen]``.
    """
    eta, r, S = cl_score_channels(F_pad, theta, mask, bias, kind=kind,
                                  interpret=interpret)
    scale = F_pad.shape[1] / max(int(n_seen), 1)
    return eta, r, S * scale
