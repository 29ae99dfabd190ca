"""Fused Newton-step statistics in the degree-bucket layout.

The batched engine (:mod:`repro.core.batched`) solves every node of a
degree bucket simultaneously: designs live as a channelized ``(k, C, d, n)``
tensor and each damped Newton iteration needs, per node, the score vector

    g = sum_n Z[:, :, :, n] r[:, :, n]           (flat (k, d*C))

and the curvature Gram

    K = sum_n Z kappa Z                          ((k, d*C, d*C))

where ``r = dl/deta`` and ``kappa = -d2l/deta2`` come from the family
epilogue. This module emits BOTH directly in that bucket layout in one
fused pass — eta, r and kappa never materialize in HBM between the design
contraction and the score/Gram contraction:

* :func:`bucket_newton_stats_ref` — the jnp reference. Its contraction
  forms are kept **identical** to the engine's historical einsums
  (including the C = 1 single-channel fast path), so swapping the engine
  onto this entry point is bit-stable — the 1e-10 golden fixtures pin it.
* :func:`bucket_newton_stats` — the Pallas kernel: grid over (bucket node,
  sample tile), epilogue residual + curvature on the VPU, g and K
  accumulated on-chip across sample tiles. ``d`` and ``d*C`` are the tiny
  per-node design dims (engine buckets pad degree to powers of four), so
  the sample axis is the only tiled one; every other block dim equals its
  full array dim, which is what the TPU lowering accepts for dims that are
  not (8, 128)-aligned. A :class:`~repro.kernels.cl.autotune.TileConfig`
  supplies the sample tile (``bm``).

Both dispatch on the static epilogue ``kind``; coordinate-major flat layout
``[(d0,c0), (d0,c1), ..., (d1,c0), ...]`` matches ``family.beta`` exactly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .epilogues import require_epilogue
from .kernel import target_platform

BNK = 128   # default sample-axis tile


def _lead(eta_kcn):
    """(k, C, n) channel-middle -> (C, k, n) leading-channel (pure layout)."""
    return jnp.moveaxis(eta_kcn, 1, 0)


def _unlead(a_ckn):
    return jnp.moveaxis(a_ckn, 0, 1)


def bucket_residual_curvature(kind: str, eta, xi):
    """Epilogue residual r (k, C, n) and curvature kappa (k, C, C, n) at
    bucket-layout logits ``eta`` (k, C, n) for targets ``xi`` (k, n)."""
    ep = require_epilogue(kind)
    C = eta.shape[1]
    el = _lead(eta)                               # (C, k, n)
    F = ep.features(xi, C)                        # (C, k, n)
    r = _unlead(ep.residual(F, el))               # (k, C, n)
    kap = jnp.moveaxis(ep.curvature(F, el), (0, 1), (1, 2))  # (k, C, C, n)
    return r, kap


def bucket_newton_stats_ref(kind: str, Zb, base, xi, W, sw=None):
    """(g, K) un-normalized score vector and curvature Gram, jnp reference.

    Zb: (k, C, d, n) bucket design; base: (k, C, n) fixed-offset logits;
    xi: (k, n) targets; W: (k, d*C) coordinate-major flat parameters;
    sw: optional (k, n) sample weights (None = unweighted). Returns
    g (k, d*C) and K (k, d*C, d*C); the engine divides by its own sample
    denominator and negates K into the Newton system.
    """
    k, C, d, _ = Zb.shape
    dC = d * C
    if C == 1:
        Z1 = Zb[:, 0]
        eta = base + jnp.einsum("kdn,kd->kn", Z1, W)[:, None, :]
        r, kap = bucket_residual_curvature(kind, eta, xi)
        if sw is not None:
            r = r * sw[:, None, :]
            kap = kap * sw[:, None, None, :]
        g = jnp.einsum("kdn,kn->kd", Z1, r[:, 0])
        K = (Z1 * kap[:, 0, 0][:, None, :]) @ jnp.swapaxes(Z1, 1, 2)
        return g, K
    eta = base + jnp.einsum("kcdn,kdc->kcn", Zb, W.reshape(k, d, C))
    r, kap = bucket_residual_curvature(kind, eta, xi)
    if sw is not None:
        r = r * sw[:, None, :]
        kap = kap * sw[:, None, None, :]
    g = jnp.einsum("kcdn,kcn->kdc", Zb, r).reshape(k, dC)
    K = jnp.einsum("kcdn,kcen,kefn->kdcfe", Zb, kap, Zb).reshape(k, dC, dC)
    return g, K


# ------------------------------------------------------------ pallas kernel
def _newton_kernel(z_ref, base_ref, xi_ref, sw_ref, w_ref, g_ref, k_ref, *,
                   kind: str, weighted: bool):
    """One (bucket node, sample tile) grid step; every value stays >= 2-D.

    Refs (leading bucket axis squeezed by the BlockSpecs): z (C, d, bm),
    base (C, 1, bm), xi / sw (1, bm), w (C, 1, d) channel-major; outputs
    g (C, 1, d) and K (C, C, d, d) accumulate across sample tiles.
    """
    t = pl.program_id(1)
    ep = require_epilogue(kind)
    C = z_ref.shape[0]
    # float32 operands contract in float32: the MXU's default precision
    # would round them to bfloat16
    prec = jax.lax.Precision.HIGHEST
    nt = (((1,), (1,)), ((), ()))                 # contract the sample axis

    @pl.when(t == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        k_ref[...] = jnp.zeros_like(k_ref)

    Z = [z_ref[c].astype(jnp.float32) for c in range(C)]          # (d, bm)
    eta = jnp.stack([
        base_ref[c].astype(jnp.float32)
        + jnp.dot(w_ref[c].astype(jnp.float32), Z[c], precision=prec,
                  preferred_element_type=jnp.float32)
        for c in range(C)])                                    # (C, 1, bm)
    F = ep.features(xi_ref[...].astype(jnp.float32), C)        # (C, 1, bm)
    r = ep.residual(F, eta)                                    # (C, 1, bm)
    kap = ep.curvature(F, eta)                                 # (C, C, 1, bm)
    if weighted:
        w = sw_ref[...].astype(jnp.float32)
        r = r * w
        kap = kap * w
    for c in range(C):
        g_ref[c] += jax.lax.dot_general(
            r[c], Z[c], nt, precision=prec,
            preferred_element_type=jnp.float32)                # (1, d)
        for e in range(C):
            k_ref[c, e] += jax.lax.dot_general(
                Z[c] * kap[c, e], Z[e], nt, precision=prec,
                preferred_element_type=jnp.float32)            # (d, d)


@functools.partial(jax.jit, static_argnames=("kind", "interpret", "tiles"))
def bucket_newton_stats(kind: str, Zb, base, xi, W, sw=None, *,
                        interpret: Optional[bool] = None, tiles=None):
    """Pallas-fused (g, K) bucket Newton statistics; see module docstring.

    Same contract as :func:`bucket_newton_stats_ref`. ``interpret=None``
    derives from the backend (compiled on TPU, interpret elsewhere —
    Pallas cannot compile on CPU). ``tiles`` is an optional
    :class:`~repro.kernels.cl.autotune.TileConfig` whose ``bm`` sets the
    sample tile (default 128). Sample padding is exact: padded design
    entries are zero, so every contraction term they touch vanishes.

    The kernel works channel-major — W in as (k, C, 1, d) rows, g out as
    (k, C, 1, d) and K as (k, C, C, d, d) per-channel-pair blocks — and the
    permutation to and from the coordinate-major flat layout happens here,
    in jnp, outside the kernel.
    """
    require_epilogue(kind)
    if interpret is None:
        interpret = target_platform() != "tpu"
    bm = BNK if tiles is None or tiles.bm is None else int(tiles.bm)
    k, C, d, n = Zb.shape
    dC = d * C
    pad_n = (-n) % bm
    Zp = jnp.pad(Zb, ((0, 0), (0, 0), (0, 0), (0, pad_n)))
    bp = jnp.pad(base, ((0, 0), (0, 0), (0, pad_n)))[:, :, None, :]
    xp = jnp.pad(xi, ((0, 0), (0, pad_n)))[:, None, :]
    weighted = sw is not None
    swp = (jnp.pad(sw, ((0, 0), (0, pad_n)))[:, None, :] if weighted
           else jnp.zeros((k, 1, n + pad_n), Zb.dtype))
    Wc = jnp.swapaxes(W.reshape(k, d, C), 1, 2)[:, :, None, :]  # (k, C, 1, d)

    g, K = pl.pallas_call(
        functools.partial(_newton_kernel, kind=kind, weighted=weighted),
        grid=(k, (n + pad_n) // bm),
        in_specs=[
            pl.BlockSpec((None, C, d, bm), lambda a, t: (a, 0, 0, t)),
            pl.BlockSpec((None, C, 1, bm), lambda a, t: (a, 0, 0, t)),
            pl.BlockSpec((None, 1, bm), lambda a, t: (a, 0, t)),
            pl.BlockSpec((None, 1, bm), lambda a, t: (a, 0, t)),
            pl.BlockSpec((None, C, 1, d), lambda a, t: (a, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, C, 1, d), lambda a, t: (a, 0, 0, 0)),
            pl.BlockSpec((None, C, C, d, d), lambda a, t: (a, 0, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, C, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((k, C, C, d, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(Zp, bp, xp, swp, Wc)
    # channel-major -> coordinate-major: g[(d,c)], K[(d,c),(f,e)]
    g = jnp.swapaxes(g[:, :, 0, :], 1, 2).reshape(k, dC)
    K = jnp.transpose(K, (0, 3, 1, 4, 2)).reshape(k, dC, dC)
    return g, K
