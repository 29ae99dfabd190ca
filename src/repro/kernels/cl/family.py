"""Adapters from a :class:`ModelFamily` to the channelized kernel inputs.

The fused pipeline speaks (C, n, p) feature stacks and (C, p, p) coupling
slabs; model families speak flat block-ordered theta vectors over a graph.
This module is the (one-way) bridge: it depends only on the family object's
public hooks (``block_dim``, ``edge_features``, ``coupling_tensor``,
``node_params``, ``kernel_kind``), never on :mod:`repro.core` itself, so
the kernel layer stays import-cycle-free.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...telemetry.recorder import active_span, record_d2h
from .kernel import cl_score_channels, target_platform


def family_kernel_inputs(family, graph, theta, X):
    """(F, theta_c, mask, bias) channelized kernel inputs.

    theta is the family's flat [node blocks, edge blocks] vector; X is the
    raw (n, p) sample matrix. Returns F (C, n, p) per-channel design
    features, theta_c (C, p, p) symmetric per-channel couplings, the (p, p)
    adjacency mask and bias (C, p) node blocks.
    """
    X = jnp.asarray(X)
    theta = jnp.asarray(theta, X.dtype)
    F = jnp.moveaxis(family.edge_features(X), -1, 0)       # (C, n, p)
    theta_c = jnp.moveaxis(family.coupling_tensor(graph, theta), -1, 0)
    mask = jnp.asarray(graph.adjacency, X.dtype)
    bias = family.node_params(graph, theta).T              # (C, p)
    return F, theta_c, mask, bias


def family_score_stats(family, graph, theta, X, *,
                       interpret: Optional[bool] = None,
                       use_pallas: Optional[bool] = None):
    """Fused (eta, r, S) channelized score statistics for any family whose
    ``kernel_kind`` has a registered epilogue. Shapes as in
    :func:`repro.kernels.cl.kernel.cl_score_channels`.

    ``use_pallas=None`` picks the backend default through the dispatch
    layer (:func:`repro.kernels.cl.ops.score_stats_channels_op`): the
    compiled Mosaic kernel on TPU, the XLA-compiled tiled twin
    elsewhere — and records the resolved path in telemetry. ``use_pallas=
    True`` forces the Pallas kernel (``interpret=None`` compiles where the
    backend can, interpret mode on CPU or on explicit ``interpret=True`` —
    the validation spelling, ~10x the reference's cost); ``False`` forces
    the jnp reference.
    """
    from .ops import score_stats_channels_op
    F, theta_c, mask, bias = family_kernel_inputs(family, graph, theta, X)
    if use_pallas is None or not use_pallas:
        return score_stats_channels_op(F, theta_c, mask, bias,
                                       kind=family.kernel_kind,
                                       use_pallas=use_pallas,
                                       interpret=interpret)
    if interpret is None:
        interpret = target_platform() != "tpu"
    return cl_score_channels(F, theta_c, mask, bias,
                             kind=family.kernel_kind,
                             interpret=interpret)


def fused_pseudo_score(family, graph, theta, x_pad, n_seen: int, *,
                       interpret: Optional[bool] = None,
                       use_pallas: Optional[bool] = None) -> np.ndarray:
    """Exact flat gradient of the average pseudo-likelihood at ``theta``
    over the first ``n_seen`` rows of a zero-padded sample buffer, via one
    fused kernel pass.

    Works for every registered epilogue kind, multi-channel included:
    channel-c singleton gradients are live-row means of ``r_c`` and the
    edge-(i, j) channel-c gradient is ``S[c, c][i, j] + S[c, c][j, i]``
    (padded rows have all-zero feature rows — for Potts because state 0 is
    the reference state — so only the Gram normalizer needs rescaling).
    """
    p = graph.p
    C = family.block_dim
    theta32 = jnp.asarray(np.asarray(theta), jnp.float32)
    x_pad = jnp.asarray(x_pad, jnp.float32)
    eta, r, S = family_score_stats(family, graph, theta32, x_pad,
                                   interpret=interpret,
                                   use_pallas=use_pallas)
    n_seen = int(n_seen)
    # the wait for the kernel stays outside the fetch's span
    jax.block_until_ready((r, S))
    with active_span("score_fetch"):
        record_d2h("score", S, r)
        S = np.asarray(S, dtype=np.float64) * (x_pad.shape[0]
                                               / max(n_seen, 1))
        r = np.asarray(r, dtype=np.float64)[:, :n_seen, :]  # live rows only
    g = np.zeros(family.n_params(graph))
    g[: p * C] = (r.sum(axis=1) / max(n_seen, 1)).T.reshape(p * C)
    for k, (i, j) in enumerate(graph.edges):
        for c in range(C):
            g[p * C + k * C + c] = S[c, c, i, j] + S[c, c, j, i]
    return g
