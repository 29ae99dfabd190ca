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
* :func:`bucket_newton_prepare` — the Pallas kernel, in two steps. The
  bucket is laid out for the kernel once (sample and node padding, the
  relayout of Z, base, xi and sw); the returned :class:`BucketNewton` is
  then called with W once per Newton iteration and runs only the kernel.
  :func:`bucket_newton_stats` is the one-shot composition of the two.

The kernel's grid is (node block, sample tile): one step covers ``kb``
bucket nodes x ``bm`` samples, with g and K accumulated on-chip across the
sample tiles in float32. Per-node streams (xi, sw, each channel of base)
arrive as dense ``(kb, bm)`` tiles with the nodes on sublanes; an
unweighted fit streams no sample weights. Two forms, chosen by the flat
block width ``d*C`` (:func:`vpu_form`):

* narrow buckets (the lattice's d = 5) take the VPU form. Z is laid out
  ``(C*d, k, n)`` so each design coordinate of a node block is a dense
  ``(kb, bm)`` tile; eta is a sum of ``W[:, j] * Z[j]`` products, and
  every score entry and every upper-triangle Gram entry is a running
  product sum, all elementwise float32 on (8, 128) register tiles, reduced
  across lanes once per grid step into one packed 128-lane row per node.
* wide buckets (the fleet's hubs) take the MXU form: Z stays
  ``(k, C, d, n)`` and the kernel loops over the block's nodes, each
  contracting its ``(d, bm)`` design on the MXU at ``Precision.HIGHEST``.

Zero padding is invisible: padded samples and padded nodes have zero
design entries, so every score and Gram term they touch vanishes. The tile
rule (:func:`newton_tile_rule`) picks ``(kb, bm)`` from the bucket's shape
under a VMEM budget; a :class:`~repro.kernels.cl.autotune.TileConfig` may
override it.

Both forms dispatch on the static epilogue ``kind``; coordinate-major flat
layout ``[(d0,c0), (d0,c1), ..., (d1,c0), ...]`` matches ``family.beta``
exactly.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .epilogues import require_epilogue
from .kernel import target_platform

LANES = 128
#: widest flat block d*C the VPU form takes: its running sums (d*C score
#: entries and d*C (d*C + 1) / 2 Gram entries, one register tile each) and
#: the design tiles stay inside the 64-tile vector register file
VPU_MAX_DC = 6
#: VMEM the kernel's blocks may take, double-buffered and padded to the
#: (8, 128) tiling: below the 16 MiB a v5e core scopes for a kernel by default
VMEM_BUDGET = 12 * 2 ** 20
#: fixed cost of one grid step on a v5e: 262,144 steps of one node x 128
#: samples took about 60 ms a Newton iteration on a 4096-row bucket
STEP_S = 0.23e-6
#: v5e HBM bandwidth, bytes/s
HBM_BYTES_PER_S = 819e9
#: widest sample tile the rule considers
MAX_BM = 8192
#: lane chunks of the VPU form per loop iteration: two took the lattice
#: bucket's kernel from 1.63 to 1.30 ms a call on a v5e, four no further
LANE_UNROLL = 2


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


# ------------------------------------------------------------- the tile rule
def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _sublanes(itemsize: int) -> int:
    """Rows of one (sublane, 128) tile: 8 for float32, 16 for bfloat16."""
    return 8 * 4 // itemsize


def vpu_form(d: int, C: int) -> bool:
    """Whether a bucket of flat block width ``d*C`` takes the VPU form."""
    return d * C <= VPU_MAX_DC


def node_block(k: int, kb: int, sub: int = 8) -> Tuple[int, int]:
    """(node block, padded node count) a call on ``k`` nodes uses.

    A block at least as large as the bucket (padded to ``sub`` rows) is the
    whole bucket, so a bucket of at most ``sub`` nodes is one block of its
    own size and is never padded; otherwise the bucket is padded with
    zero-design nodes up to a multiple of the block.
    """
    if k <= sub:
        return k, k
    kb = min(int(kb), _round_up(k, sub))
    return kb, _round_up(k, kb)


def newton_grid_steps(k: int, n: int, kb: int, bm: int,
                      sub: int = 8) -> int:
    """Grid steps of one kernel call: node blocks x sample tiles."""
    kb, k_pad = node_block(k, kb, sub)
    return (k_pad // kb) * (-(-n // bm))


def _streams(d: int, C: int, weighted: bool) -> int:
    """(kb, bm) tiles each grid step reads: Z's d*C, base's C, xi, sw."""
    return d * C + C + 1 + int(weighted)


def newton_vmem_bytes(kb: int, bm: int, *, d: int, C: int, weighted: bool,
                      sub: int = 8) -> int:
    """VMEM of the kernel's blocks at one (kb, bm), double-buffered, with
    every block padded to whole (sub, 128) tiles (counted at 4 bytes an
    element); the MXU form adds room for one node's design-sized values."""
    rows, lanes = _round_up(kb, sub), _round_up(bm, LANES)
    dC = d * C
    if vpu_form(d, C):
        tiles = _streams(d, C, weighted) * rows * lanes
        small = rows * _round_up(dC, LANES) + rows * _round_up(
            _vpu_stats(dC), LANES)                         # W in, stats out
        return 4 * (2 * (tiles + small))
    per_node = C * _round_up(d, sub) * lanes
    tiles = kb * per_node + (_streams(d, C, weighted) - dC) * rows * lanes
    small = kb * C * (sub * _round_up(d, LANES)                # W in, g out
                      + sub * _round_up(d, LANES)
                      + C * _round_up(d, sub) * _round_up(d, LANES))  # K
    return 4 * (2 * (tiles + small) + 3 * per_node)


def newton_tile_rule(k: int, d: int, C: int, n: int, weighted: bool, *,
                     bm: Optional[int] = None,
                     itemsize: int = 4) -> Tuple[int, int]:
    """(kb, bm) for one bucket shape on the TPU. Deterministic.

    Among sample tiles that are 128-multiples up to the padded sample axis
    (or the given ``bm``) and node blocks that are ``sub``-multiples up to
    the padded bucket (:func:`node_block` cuts a block to a bucket of at
    most ``sub`` nodes), whose blocks fit :data:`VMEM_BUDGET`, take the pair
    of least modelled time: a fixed cost per grid step plus the bytes the
    padded streams read from HBM. Ties go to the wider sample tile, whose
    per-step lane reduction is amortized over more samples.
    """
    sub = _sublanes(itemsize)
    n_top = _round_up(max(n, 1), LANES)
    if bm is not None:
        lanes = [int(bm)]
    else:
        lanes = [b for b in (LANES << i for i in range(16))
                 if b < n_top and b <= MAX_BM]
        lanes = sorted(set(lanes + [min(n_top, MAX_BM)]), reverse=True)
    if k <= sub:
        blocks = [sub]
    else:
        k_top = _round_up(k, sub)
        blocks = sorted({b for b in (sub << i for i in range(16))
                         if b < k_top} | {k_top}, reverse=True)
    rows = _streams(d, C, weighted)
    best, best_cost = None, math.inf
    for b_m in lanes:
        for kb in blocks:
            if newton_vmem_bytes(kb, b_m, d=d, C=C, weighted=weighted,
                                 sub=sub) > VMEM_BUDGET:
                continue
            steps = newton_grid_steps(k, n, kb, b_m, sub)
            nbytes = 4.0 * rows * node_block(k, kb, sub)[1] \
                * _round_up(n, b_m)
            cost = steps * STEP_S + nbytes / HBM_BYTES_PER_S
            if cost < best_cost:
                best, best_cost = (kb, b_m), cost
    if best is None:        # nothing fits: the smallest legal blocks
        best = (blocks[-1], lanes[-1])
    return best


# --------------------------------------------------------------- the kernels
def _vpu_stats(dC: int) -> int:
    """Packed statistics per node: d*C score entries, then the Gram's upper
    triangle row by row."""
    return dC + dC * (dC + 1) // 2


def _gram_lanes(dC: int) -> np.ndarray:
    """(dC*dC,) lane of each K[p, q] in the packed row (K is symmetric)."""
    tri = {}
    for p in range(dC):
        for q in range(p, dC):
            tri[p, q] = dC + len(tri)
    return np.asarray([tri[min(p, q), max(p, q)] for p in range(dC)
                       for q in range(dC)], dtype=np.int32)


def _vpu_kernel(*refs, kind: str, weighted: bool, C: int, d: int, rc: int,
                lc: int):
    """One (node block, sample tile) step of the VPU form.

    Refs: z (C*d, kb, bm) channel-major coordinates, base (C, kb, bm),
    xi (kb, bm), sw (kb, bm) when ``weighted``, w (kb, d*C) flat; out
    (kb, lanes) packed statistics, accumulated across sample tiles. The
    block is walked in (rc, lc) register tiles: for each rc-row chunk the
    sample tile's lane chunks fold into one running tile per statistic,
    reduced across lanes at the end of the step.
    """
    if weighted:
        z_ref, b_ref, x_ref, s_ref, w_ref, o_ref = refs
    else:
        z_ref, b_ref, x_ref, w_ref, o_ref = refs
    ep = require_epilogue(kind)
    dC = d * C
    pairs = [(p, q) for p in range(dC) for q in range(p, dC)]
    kb, bm = x_ref.shape
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def row_chunk(ri, carry):
        R = pl.ds(pl.multiple_of(ri * rc, rc), rc)
        # flat coordinate p = j*C + c: channel c = p % C, coordinate j = p // C
        w = [jnp.broadcast_to(w_ref[R, pl.ds(p, 1)].astype(f32), (rc, lc))
             for p in range(dC)]

        def lane_chunk(l0, acc):
            L = pl.ds(pl.multiple_of(l0 * lc, lc), lc)
            z = [z_ref[(p % C) * d + p // C, R, L].astype(f32)
                 for p in range(dC)]
            eta = jnp.stack([
                functools.reduce(
                    jnp.add, [w[p] * z[p] for p in range(c, dC, C)],
                    b_ref[c, R, L].astype(f32))
                for c in range(C)])                       # (C, rc, lc)
            F = ep.features(x_ref[R, L].astype(f32), C)
            r = ep.residual(F, eta)                       # (C, rc, lc)
            kap = ep.curvature(F, eta)                    # (C, C, rc, lc)
            if weighted:
                s = s_ref[R, L].astype(f32)
                r = r * s
                kap = kap * s
            kz = {(c, q): kap[c, q % C] * z[q]
                  for c in range(C) for q in range(dC)}
            return tuple(
                [acc[p] + r[p % C] * z[p] for p in range(dC)]
                + [acc[dC + i] + z[p] * kz[p % C, q]
                   for i, (p, q) in enumerate(pairs)])

        unroll = math.gcd(LANE_UNROLL, bm // lc)

        def lane_chunks(li, acc):
            for u in range(unroll):
                acc = lane_chunk(li * unroll + u, acc)
            return acc

        zero = jnp.zeros((rc, lc), f32)
        acc = jax.lax.fori_loop(0, bm // lc // unroll, lane_chunks,
                                (zero,) * _vpu_stats(dC))
        lane = jax.lax.broadcasted_iota(jnp.int32, (rc, o_ref.shape[1]), 1)
        row = functools.reduce(jnp.add, [
            jnp.where(lane == s, jnp.sum(a, axis=1, keepdims=True), 0.0)
            for s, a in enumerate(acc)])
        o_ref[R, :] += row
        return carry

    jax.lax.fori_loop(0, kb // rc, row_chunk, 0)


def _mxu_kernel(*refs, kind: str, weighted: bool):
    """One (node block, sample tile) step of the MXU form.

    Refs: z (kb, C, d, bm), base (C, kb, bm), xi (kb, bm), sw (kb, bm) when
    ``weighted``, w (kb, C, 1, d) channel-major; outputs g (kb, C, 1, d)
    and K (kb, C, C, d, d), accumulated across sample tiles. Loops over the
    block's nodes; every value stays >= 2-D.
    """
    if weighted:
        z_ref, b_ref, x_ref, s_ref, w_ref, g_ref, k_ref = refs
    else:
        z_ref, b_ref, x_ref, w_ref, g_ref, k_ref = refs
    ep = require_epilogue(kind)
    kb, C = z_ref.shape[:2]
    f32 = jnp.float32
    # float32 operands contract in float32: the MXU's default precision
    # would round them to bfloat16
    prec = jax.lax.Precision.HIGHEST
    nt = (((1,), (1,)), ((), ()))                 # contract the sample axis

    @pl.when(pl.program_id(1) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        k_ref[...] = jnp.zeros_like(k_ref)

    def node(i, carry):
        row = pl.ds(i, 1)
        Z = [z_ref[i, c].astype(f32) for c in range(C)]            # (d, bm)
        eta = jnp.stack([
            b_ref[c, row, :].astype(f32)
            + jnp.dot(w_ref[i, c].astype(f32), Z[c], precision=prec,
                      preferred_element_type=f32)
            for c in range(C)])                                # (C, 1, bm)
        F = ep.features(x_ref[row, :].astype(f32), C)           # (C, 1, bm)
        r = ep.residual(F, eta)
        kap = ep.curvature(F, eta)                             # (C, C, 1, bm)
        if weighted:
            s = s_ref[row, :].astype(f32)
            r = r * s
            kap = kap * s
        for c in range(C):
            g_ref[i, c] += jax.lax.dot_general(
                r[c], Z[c], nt, precision=prec, preferred_element_type=f32)
            for e in range(C):
                k_ref[i, c, e] += jax.lax.dot_general(
                    Z[c] * kap[c, e], Z[e], nt, precision=prec,
                    preferred_element_type=f32)                # (d, d)
        return carry

    jax.lax.fori_loop(0, kb, node, 0)


class BucketNewton:
    """A bucket laid out once for the Newton kernel; call it with W.

    Built by :func:`bucket_newton_prepare`. ``node_block``, ``bm`` and
    ``grid_steps`` (steps per call, i.e. per Newton iteration) describe
    the grid each call runs.
    """

    def __init__(self, kind, shape, streams, *, kb, bm, k_pad, sub,
                 weighted, interpret):
        self.kind = kind
        self.k, self.C, self.d, self.n = shape
        self.streams = tuple(streams)
        self.node_block, self.bm, self.k_pad, self.sub = kb, bm, k_pad, sub
        self.weighted, self.interpret = weighted, interpret
        self.grid_steps = newton_grid_steps(self.k, self.n, kb, bm, sub)
        self.vpu = vpu_form(self.d, self.C)

    def __call__(self, W):
        """(g (k, d*C), K (k, d*C, d*C)) at flat parameters W (k, d*C)."""
        k, C, d = self.k, self.C, self.d
        dC, kb, bm = d * C, self.node_block, self.bm
        n_pad = self.streams[0].shape[-1]
        grid = (self.k_pad // kb, n_pad // bm)
        tile = pl.BlockSpec((kb, bm), lambda a, t: (a, t))
        chans = pl.BlockSpec((C, kb, bm), lambda a, t: (0, a, t))
        per_node = [chans, tile] + ([tile] if self.weighted else [])
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        pad = ((0, self.k_pad - k), (0, 0))
        if self.vpu:
            rc = self.sub if kb % self.sub == 0 else kb
            lc = LANES if bm % LANES == 0 else bm
            width = _round_up(_vpu_stats(dC), LANES)
            out = pl.pallas_call(
                functools.partial(_vpu_kernel, kind=self.kind,
                                  weighted=self.weighted, C=C, d=d, rc=rc,
                                  lc=lc),
                name="bucket_newton_stats",
                grid=grid,
                in_specs=[pl.BlockSpec((dC, kb, bm),
                                       lambda a, t: (0, a, t))]
                + per_node + [pl.BlockSpec((kb, dC), lambda a, t: (a, 0))],
                out_specs=pl.BlockSpec((kb, width), lambda a, t: (a, 0)),
                out_shape=jax.ShapeDtypeStruct((self.k_pad, width),
                                               jnp.float32),
                compiler_params=params,
                interpret=self.interpret,
            )(*self.streams, jnp.pad(W, pad))
            g = out[:k, :dC]
            K = out[:k, _gram_lanes(dC)].reshape(k, dC, dC)
            return g, K
        Wc = jnp.swapaxes(W.reshape(k, d, C), 1, 2)[:, :, None, :]
        Wc = jnp.pad(Wc, pad + ((0, 0),) * 2)               # (k_pad, C, 1, d)
        g, K = pl.pallas_call(
            functools.partial(_mxu_kernel, kind=self.kind,
                              weighted=self.weighted),
            name="bucket_newton_stats",
            grid=grid,
            in_specs=[pl.BlockSpec((kb, C, d, bm),
                                   lambda a, t: (a, 0, 0, t))]
            + per_node
            + [pl.BlockSpec((kb, C, 1, d), lambda a, t: (a, 0, 0, 0))],
            out_specs=[
                pl.BlockSpec((kb, C, 1, d), lambda a, t: (a, 0, 0, 0)),
                pl.BlockSpec((kb, C, C, d, d),
                             lambda a, t: (a, 0, 0, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((self.k_pad, C, 1, d), jnp.float32),
                jax.ShapeDtypeStruct((self.k_pad, C, C, d, d), jnp.float32),
            ],
            compiler_params=params,
            interpret=self.interpret,
        )(*self.streams, Wc)
        # channel-major -> coordinate-major: g[(d,c)], K[(d,c),(f,e)]
        g = jnp.swapaxes(g[:k, :, 0, :], 1, 2).reshape(k, dC)
        K = jnp.transpose(K[:k], (0, 3, 1, 4, 2)).reshape(k, dC, dC)
        return g, K


def bucket_newton_prepare(kind: str, Zb, base, xi, sw=None, *,
                          interpret: Optional[bool] = None,
                          tiles=None) -> BucketNewton:
    """Lay one bucket out for the Newton kernel; returns a W -> (g, K) call.

    Same operands as :func:`bucket_newton_stats_ref` less W. Everything
    that does not depend on W happens here, once: sample and node padding
    and the relayout of Z, base, xi and sw. A Newton loop prepares before
    it starts and calls the result once per iteration, so its body runs
    the kernel and no pad or copy of the bucket. ``interpret=None``
    derives from the backend (compiled on TPU, interpret elsewhere —
    Pallas cannot compile on CPU). ``tiles`` is an optional
    :class:`~repro.kernels.cl.autotune.TileConfig`: its ``kb`` and ``bm``
    set the node block and sample tile, and either left ``None`` comes
    from :func:`newton_tile_rule`.
    """
    require_epilogue(kind)
    if interpret is None:
        interpret = target_platform() != "tpu"
    k, C, d, n = Zb.shape
    weighted = sw is not None
    itemsize = jnp.dtype(Zb.dtype).itemsize
    sub = _sublanes(itemsize)
    kb = None if tiles is None else tiles.kb
    bm = None if tiles is None else tiles.bm
    if kb is None or bm is None:
        rule_kb, bm = newton_tile_rule(k, d, C, n, weighted, bm=bm,
                                       itemsize=itemsize)
        kb = rule_kb if kb is None else kb
    kb, k_pad = node_block(k, int(kb), sub)
    bm = int(bm)
    n_pad = _round_up(n, bm)

    def pad(a, k_axis):
        widths = [(0, 0)] * a.ndim
        widths[k_axis] = (0, k_pad - k)
        widths[-1] = (0, n_pad - n)
        return jnp.pad(a, widths)

    if vpu_form(d, C):
        z = pad(Zb, 0).transpose(1, 2, 0, 3).reshape(C * d, k_pad, n_pad)
    else:
        z = pad(Zb, 0)
    streams = [z, pad(_lead(base), 1), pad(xi, 0)]
    if weighted:
        streams.append(pad(sw, 0))
    # materialize the streams here: XLA would otherwise sink a constant
    # stream (the all-zero base of a fit with free singletons) into the
    # Newton loop and rebuild it on every iteration
    streams = jax.lax.optimization_barrier(streams)
    return BucketNewton(kind, (k, C, d, n), streams, kb=kb, bm=bm,
                        k_pad=k_pad, sub=sub, weighted=weighted,
                        interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("kind", "interpret", "tiles"))
def bucket_newton_stats(kind: str, Zb, base, xi, W, sw=None, *,
                        interpret: Optional[bool] = None, tiles=None):
    """Pallas-fused (g, K) bucket Newton statistics; see module docstring.

    Same contract as :func:`bucket_newton_stats_ref`: the one-shot
    composition of :func:`bucket_newton_prepare` and its call.
    """
    return bucket_newton_prepare(kind, Zb, base, xi, sw,
                                 interpret=interpret, tiles=tiles)(W)
