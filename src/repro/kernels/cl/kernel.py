"""Pallas TPU kernels: the family-generic fused CL pipeline.

Two kernels share one channelized skeleton:

* :func:`cl_logits` — the masked conditional-logit matmul
  ``eta_c = F_c @ (Theta_c * A) + b_c`` (the seed's ``ising_cl_logits`` is
  its C = 1 instance);
* :func:`cl_score_channels` — the whole fused score pipeline in ONE pass
  over the samples:

      eta_c = F_c @ (Theta_c * A) + b_c      (masked MXU matmul, per channel)
      r     = epilogue.residual(F_self, eta) (VPU, all C channels together)
      S[c,e] = r_c^T F_e / n                 (cross-channel score Gram)

The per-family residual comes from the epilogue registry
(:mod:`repro.kernels.cl.epilogues`) and is dispatched **at trace time** by
the static ``kind`` argument — one compiled kernel per family kind.
Multi-channel families (Potts, C = q - 1 softmax channels) run the same
skeleton as Ising/Gaussian: the channel axis is carried whole inside every
tile (C is small — q - 1 for Potts, 1 otherwise), so the softmax residual
sees all channels of a node's logits at once and the Gram epilogue emits
the full (C, C) grid of cross-channel blocks.

``r`` is the per-sample score residual every gradient statistic is built
from: channel-c column means of ``r_c`` are the singleton-block gradients of
the average pseudo-likelihood and ``S[c, c][i, j] + S[c, c][j, i]`` (for an
edge (i, j)) its coupling-block gradients; the off-diagonal ``S[c, e]``
blocks are the cross-channel score products the second-order (sandwich /
Gram) machinery consumes. Fusing the epilogue and the Gram contraction
means F is read from HBM once and eta never round-trips.

Grid is (j, i, k): j tiles output columns (and S rows), i tiles samples,
k tiles the contraction. The F strip for the current sample tile is stashed
in VMEM during the k loop, so the Gram contraction re-reads it from on-chip
memory rather than HBM. Tile sizes default to the MXU-aligned 128s and are
tunable through a :class:`~repro.kernels.cl.autotune.TileConfig` (static
``tiles=`` argument); operand shapes never have to divide the tiles —
every axis is zero-padded up to the tile grid and sliced back, and the
padding is provably invisible (zero feature rows/columns contribute
nothing to any contraction; the edge-tile hypothesis properties pin it).
``interpret=None`` derives from the backend: compiled on TPU, interpret
(the Python-speed validation mode) elsewhere — Pallas cannot compile on
CPU, where the dispatch layer uses :mod:`.tiled` instead. Float32 operands
contract at ``Precision.HIGHEST``, so a float32 plan means float32 on the
TPU too (the MXU default rounds operands to bfloat16).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .epilogues import require_epilogue

BM, BN, BK = 128, 128, 128


def target_platform() -> str:
    """Platform the computation being traced will run on: that of the
    ``jax.default_device`` in scope when one is set (so a host-CPU run on a
    TPU machine takes the CPU paths), else the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def _precision(dtype):
    """Dot precision for operands of ``dtype``: HIGHEST for float32."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _resolve(interpret: Optional[bool], tiles):
    """(interpret, bm, bn, bk) trace-time constants from the static args."""
    if interpret is None:
        interpret = target_platform() != "tpu"
    if tiles is None:
        return interpret, BM, BN, BK
    bm = BM if tiles.bm is None else int(tiles.bm)
    return interpret, bm, int(tiles.bn), int(tiles.bk)


# ------------------------------------------------------------- logits kernel
def _logits_kernel(f_ref, theta_ref, mask_ref, bias_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    C = f_ref.shape[0]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    masked = theta_ref[...] * mask_ref[...][None]    # VPU fuse, no HBM trip
    prec = _precision(f_ref.dtype)
    for c in range(C):                               # static channel unroll
        acc_ref[c] += jnp.dot(f_ref[c], masked[c], precision=prec,
                              preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = (acc_ref[...] +
                        bias_ref[...].astype(jnp.float32)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def cl_logits(F, theta, mask, bias, *, interpret: Optional[bool] = None,
              tiles=None):
    """Channelized masked-matmul logits: eta_c = F_c @ (theta_c * mask) + b_c.

    F: (C, n, p); theta: (C, p, p); mask: (p, p); bias: (C, p). Returns
    eta of shape (C, n, p) in F.dtype. Shapes are padded to the tile grid
    internally (128s by default; ``tiles`` overrides). ``interpret=None``
    derives from the backend — compiled on TPU, interpret elsewhere.
    """
    interpret, bm, bn, bk = _resolve(interpret, tiles)
    C, n, p = F.shape
    pad_n = (-n) % bm
    pad_p = (-p) % math.lcm(bn, bk)
    fp = jnp.pad(F, ((0, 0), (0, pad_n), (0, pad_p)))
    tp = jnp.pad(theta, ((0, 0), (0, pad_p), (0, pad_p)))
    mp = jnp.pad(mask, ((0, pad_p), (0, pad_p)))
    bp = jnp.pad(bias, ((0, 0), (0, pad_p)))[:, None, :]
    _, np_, pp = fp.shape

    grid = (np_ // bm, pp // bn, pp // bk)
    out = pl.pallas_call(
        _logits_kernel,
        name="cl_logits",
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((C, bk, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((C, 1, bn), lambda i, j, k: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((C, bm, bn), lambda i, j, k: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, np_, pp), F.dtype),
        scratch_shapes=[pltpu.VMEM((C, bm, bn), jnp.float32)],
        interpret=interpret,
    )(fp, tp, mp, bp)
    return out[:, :n, :p]


def ising_cl_logits(x, theta, mask, bias, *,
                    interpret: Optional[bool] = None):
    """eta = x @ (theta * mask) + bias — the seed single-channel entry.

    x: (n, p); theta, mask: (p, p); bias: (p,). The C = 1 instance of
    :func:`cl_logits`.
    """
    return cl_logits(x[None], theta[None], mask, bias[None],
                     interpret=interpret)[0]


# -------------------------------------------------------------- score kernel
def _score_kernel_c1(x_ref, theta_ref, mask_ref, bias_ref,
                     eta_ref, r_ref, s_ref, acc_ref, xstrip_ref, *, n: int,
                     kind: str, bn: int, bk: int):
    """Single-channel (C = 1) specialization of :func:`_score_kernel`.

    Same grid, same VMEM strip, same epilogue registry — but 2-D refs
    throughout, which keeps the interpret-mode (CPU validation) path ~10x
    cheaper than carrying a unit channel axis through every ref op. Picked
    at trace time by ``cl_score_channels`` exactly like the batched
    engine's own C == 1 contraction fast path.
    """
    j = pl.program_id(0)
    i = pl.program_id(1)
    k = pl.program_id(2)
    ni = pl.num_programs(1)
    nk = pl.num_programs(2)
    epilogue = require_epilogue(kind)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((i == 0) & (k == 0))
    def _init_s():
        s_ref[...] = jnp.zeros_like(s_ref)

    xstrip_ref[:, pl.ds(k * bk, bk)] = x_ref[...].astype(jnp.float32)
    masked = theta_ref[...] * mask_ref[...]          # VPU fuse, no HBM trip
    acc_ref[...] += jnp.dot(x_ref[...], masked, precision=_precision(
        x_ref.dtype), preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        eta = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        eta_ref[...] = eta.astype(eta_ref.dtype)
        xj = xstrip_ref[:, pl.ds(j * bn, bn)]        # j-tile nodes' values
        r = epilogue.residual(xj[None], eta[None])[0]
        r_ref[...] = r.astype(r_ref.dtype)
        s_ref[...] += jnp.dot(r.T, xstrip_ref[...],
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)

    @pl.when((k == nk - 1) & (i == ni - 1))
    def _finish():
        s_ref[...] = s_ref[...] / n


def _score_kernel(f_ref, theta_ref, mask_ref, bias_ref,
                  eta_ref, r_ref, s_ref, acc_ref, fstrip_ref, *, n: int,
                  kind: str, bn: int, bk: int):
    j = pl.program_id(0)
    i = pl.program_id(1)
    k = pl.program_id(2)
    ni = pl.num_programs(1)
    nk = pl.num_programs(2)
    C = f_ref.shape[0]
    epilogue = require_epilogue(kind)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((i == 0) & (k == 0))
    def _init_s():
        s_ref[...] = jnp.zeros_like(s_ref)

    # stash this sample-tile's F strip so the Gram contraction stays on-chip
    fstrip_ref[:, :, pl.ds(k * bk, bk)] = f_ref[...].astype(jnp.float32)
    masked = theta_ref[...] * mask_ref[...][None]    # VPU fuse, no HBM trip
    prec = _precision(f_ref.dtype)
    for c in range(C):                               # static channel unroll
        acc_ref[c] += jnp.dot(f_ref[c], masked[c], precision=prec,
                              preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        eta = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        eta_ref[...] = eta.astype(eta_ref.dtype)
        # the j-tile nodes' own features = the residual's target side
        y = fstrip_ref[:, :, pl.ds(j * bn, bn)]      # (C, bm, bn)
        r = epilogue.residual(y, eta)                # all channels at once
        r_ref[...] = r.astype(r_ref.dtype)
        for c in range(C):
            for e in range(C):
                s_ref[c, e] += jnp.dot(r[c].T, fstrip_ref[e],
                                       precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=jnp.float32)

    @pl.when((k == nk - 1) & (i == ni - 1))
    def _finish():
        s_ref[...] = s_ref[...] / n


@functools.partial(jax.jit, static_argnames=("interpret", "kind", "tiles"))
@jax.named_scope("score")
def cl_score_channels(F, theta, mask, bias, *, kind: str,
                      interpret: Optional[bool] = None, tiles=None):
    """(eta, r, S) = fused channelized score statistics; see module docstring.

    F: (C, n, p) per-channel design features (for single-channel kinds
    F[0] is the raw sample matrix; for Potts, state indicators); theta:
    (C, p, p) per-channel couplings; mask: (p, p); bias: (C, p). ``kind``
    picks the family epilogue from the registry (one compiled kernel per
    kind). Returns eta, r of shape (C, n, p) in F.dtype and the
    cross-channel score Gram S of shape (C, C, p, p) in float32 with
    ``S[c, e] = r_c^T F_e / n``.

    ``interpret=None`` derives from the backend (compiled on TPU,
    interpret — the Python-speed validation mode — elsewhere); ``tiles``
    is an optional :class:`~repro.kernels.cl.autotune.TileConfig`
    overriding the 128-aligned defaults. Shapes need not divide the tiles:
    n is padded to the sample tile and p to lcm(bn, bk), and zero padding
    is invisible to every output (sliced off for eta/r, contributing
    exactly zero to S).
    """
    require_epilogue(kind)        # fail at trace time with a clear error
    interpret, bm, bn, bk = _resolve(interpret, tiles)
    C, n, p = F.shape
    pad_n = (-n) % bm
    pad_p = (-p) % math.lcm(bn, bk)
    fp = jnp.pad(F, ((0, 0), (0, pad_n), (0, pad_p)))
    tp = jnp.pad(theta, ((0, 0), (0, pad_p), (0, pad_p)))
    mp = jnp.pad(mask, ((0, pad_p), (0, pad_p)))
    bp = jnp.pad(bias, ((0, 0), (0, pad_p)))[:, None, :]
    _, np_, pp = fp.shape

    grid = (pp // bn, np_ // bm, pp // bk)
    if C == 1:
        # trace-time single-channel specialization: same skeleton, 2-D refs
        eta, r, s = pl.pallas_call(
            functools.partial(_score_kernel_c1, n=n, kind=kind, bn=bn,
                              bk=bk),
            name="cl_score_channels",
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda j, i, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda j, i, k: (k, j)),
                pl.BlockSpec((bk, bn), lambda j, i, k: (k, j)),
                pl.BlockSpec((1, bn), lambda j, i, k: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
                pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
                pl.BlockSpec((bn, pp), lambda j, i, k: (j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((np_, pp), F.dtype),
                jax.ShapeDtypeStruct((np_, pp), F.dtype),
                jax.ShapeDtypeStruct((pp, pp), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bm, bn), jnp.float32),
                pltpu.VMEM((bm, pp), jnp.float32),
            ],
            interpret=interpret,
        )(fp[0], tp[0], mp, bp[0])
        return (eta[None, :n, :p], r[None, :n, :p],
                s[None, None, :p, :p])
    eta, r, s = pl.pallas_call(
        functools.partial(_score_kernel, n=n, kind=kind, bn=bn, bk=bk),
        name="cl_score_channels",
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, bm, bk), lambda j, i, k: (0, i, k)),
            pl.BlockSpec((C, bk, bn), lambda j, i, k: (0, k, j)),
            pl.BlockSpec((bk, bn), lambda j, i, k: (k, j)),
            pl.BlockSpec((C, 1, bn), lambda j, i, k: (0, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((C, bm, bn), lambda j, i, k: (0, i, j)),
            pl.BlockSpec((C, bm, bn), lambda j, i, k: (0, i, j)),
            pl.BlockSpec((C, C, bn, pp), lambda j, i, k: (0, 0, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, np_, pp), F.dtype),
            jax.ShapeDtypeStruct((C, np_, pp), F.dtype),
            jax.ShapeDtypeStruct((C, C, pp, pp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((C, bm, bn), jnp.float32),
            pltpu.VMEM((C, bm, pp), jnp.float32),
        ],
        interpret=interpret,
    )(fp, tp, mp, bp)
    return eta[:, :n, :p], r[:, :n, :p], s[:, :, :p, :p]
