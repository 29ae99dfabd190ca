"""Compiled-CPU twins of the fused CL kernels.

Pallas cannot compile on the CPU backend (interpret mode only, and
interpret mode is a Python-speed validation tool). These entries are the
*compiled* CPU tier the dispatch layer (:mod:`.ops`) picks by default off
TPU: XLA-jitted mirrors of the Pallas kernels' tiling — the sample
axis split into chunks, per-chunk epilogue residual/curvature, and the
score/curvature Grams accumulated across chunks in a ``lax.scan`` — so
the working set per step stays cache-sized the same way a VMEM tile does.

Chunking contract (what keeps the 1e-10 goldens safe):

* ``chunk=None`` (or >= n) delegates to the jnp reference **verbatim** —
  identical contraction order, bit-identical results. This is the
  heuristic default below :data:`~repro.kernels.cl.autotune.CHUNK_MIN_N`
  samples, i.e. for every golden fixture and test shape.
* an explicit chunk reorders the float accumulation (chunk partial sums),
  which is measured to win ~1.4x on large sample axes
  (BENCH_kernels.json newton rows) at the usual reordering-jitter cost;
  the autotuner only asks for it above the threshold.

Zero-padding the sample axis up to a chunk multiple is provably invisible:
padded design/feature columns are zero, so their score and Gram
contributions vanish term-by-term (padded *residuals* need not be zero —
they are always multiplied by a zero feature entry), and per-sample
outputs are sliced back to the live rows.

Mixed precision falls out of jnp promotion: bfloat16 designs against the
float32 solver state promote every contraction to float32, so bf16 is
load/matmul-side only and the Gram accumulators are always float32 (or
float64 under x64 plans).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .epilogues import require_epilogue
from .newton import bucket_newton_stats_ref
from .ref import cl_score_channels_ref

__all__ = ["cl_score_channels_tiled", "bucket_newton_stats_tiled"]


@functools.partial(jax.jit, static_argnames=("kind", "chunk"))
@jax.named_scope("score")
def cl_score_channels_tiled(F, theta, mask, bias, *, kind: str,
                            chunk=None):
    """(eta, r, S) fused channelized score statistics, XLA-compiled.

    Same contract as :func:`repro.kernels.cl.kernel.cl_score_channels` /
    its jnp reference. ``chunk`` (static) tiles the sample axis; ``None``
    is the exact reference path (see module docstring).
    """
    require_epilogue(kind)
    C, n, p = F.shape
    if chunk is None or chunk >= n:
        return cl_score_channels_ref(F, theta, mask, bias, kind)
    ep = require_epilogue(kind)
    pad = (-n) % chunk
    Fp = jnp.pad(F, ((0, 0), (0, pad), (0, 0)))
    nt = (n + pad) // chunk
    # (nt, C, chunk, p): scan steps over sample chunks
    Fc = jnp.moveaxis(Fp.reshape(C, nt, chunk, p), 1, 0)
    tm = (theta * mask[None]).astype(jnp.float32)
    b32 = bias[:, None, :].astype(jnp.float32)

    def step(S, Ft):
        Ff = Ft.astype(jnp.float32)
        eta = jnp.einsum("cnj,cji->cni", Ff, tm) + b32
        r = ep.residual(Ff, eta)
        S = S + jnp.einsum("cni,enj->ceij", r, Ff)
        return S, (eta.astype(F.dtype), r.astype(F.dtype))

    S0 = jnp.zeros((C, C, p, p), jnp.float32)
    S, (etas, rs) = jax.lax.scan(step, S0, Fc)
    eta = jnp.moveaxis(etas, 0, 1).reshape(C, nt * chunk, p)[:, :n]
    r = jnp.moveaxis(rs, 0, 1).reshape(C, nt * chunk, p)[:, :n]
    return eta, r, S / n


@functools.partial(jax.jit, static_argnames=("kind", "chunk"))
def bucket_newton_stats_tiled(kind: str, Zb, base, xi, W, sw=None, *,
                              chunk=None):
    """(g, K) fused bucket Newton statistics, XLA-compiled.

    Same contract as :func:`repro.kernels.cl.newton.bucket_newton_stats_ref`
    (whose chunk the scan body literally calls, so the per-chunk math —
    including the C == 1 fast path — is contraction-identical). ``chunk``
    (static) tiles the sample axis; ``None`` is the exact reference path.
    """
    k, C, d, n = Zb.shape
    if chunk is None or chunk >= n:
        return bucket_newton_stats_ref(kind, Zb, base, xi, W, sw)
    pad = (-n) % chunk
    nt = (n + pad) // chunk
    Zp = jnp.pad(Zb, ((0, 0), (0, 0), (0, 0), (0, pad)))
    bp = jnp.pad(base, ((0, 0), (0, 0), (0, pad)))
    xp = jnp.pad(xi, ((0, 0), (0, pad)))
    # chunk-major: (nt, k, C, d, chunk) etc., one scan step per chunk
    Zc = jnp.moveaxis(Zp.reshape(k, C, d, nt, chunk), 3, 0)
    bc = jnp.moveaxis(bp.reshape(k, C, nt, chunk), 2, 0)
    xc = jnp.moveaxis(xp.reshape(k, nt, chunk), 1, 0)
    weighted = sw is not None
    if weighted:
        sc = jnp.moveaxis(jnp.pad(sw, ((0, 0), (0, pad)))
                          .reshape(k, nt, chunk), 1, 0)
        xs = (Zc, bc, xc, sc)
    else:
        xs = (Zc, bc, xc)

    acc_dtype = jnp.result_type(Zb.dtype, W.dtype, jnp.float32)
    dC = d * C

    def step(carry, inp):
        g, K = carry
        if weighted:
            Zt, bt, xt, st = inp
        else:
            (Zt, bt, xt), st = inp, None
        gi, Ki = bucket_newton_stats_ref(kind, Zt, bt, xt, W, st)
        return (g + gi, K + Ki), None

    g0 = jnp.zeros((k, dC), acc_dtype)
    K0 = jnp.zeros((k, dC, dC), acc_dtype)
    (g, K), _ = jax.lax.scan(step, (g0, K0), xs)
    return g, K
