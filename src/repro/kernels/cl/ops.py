"""Backend-aware dispatch for the fused CL kernels.

Four paths, one resolver:

* ``"mosaic"``    — the compiled Pallas kernel (TPU only; Pallas cannot
  compile on the CPU backend).
* ``"tiled"``     — the XLA-compiled CPU twins (:mod:`.tiled`): same tiling
  idea as the Pallas kernels, compiled through plain jit. The default off
  TPU.
* ``"ref"``       — the plain jnp reference (:mod:`.ref`).
* ``"interpret"`` — the Pallas kernel body run in Python (validation only;
  orders of magnitude slower than everything else).

The platform is the one the traced computation will run on
(:func:`.kernel.target_platform`): a ``jax.default_device`` in scope wins
over the default backend, so a host-CPU run on a TPU machine resolves to
the CPU paths (the bucket solvers' jit caches key on that setting).

Tile sizes come from the autotuner (:func:`.autotune.get_tiles`): cached
tuned tiles when a search ran, deterministic shape heuristics otherwise.

Every dispatcher tags the innermost active telemetry recorder (see
:func:`repro.telemetry.record_kernel_trace`) with the kernel kind, the
*resolved path* (``backend=`` tag), and the operand shape. The calls run
at trace time — inside jit they fire once per compiled shape, so a
telemetry log shows exactly which kernels compiled for which shapes, at
zero steady-state cost; with telemetry off the hook is a falsy list check.

Back-compat: callers keep passing ``use_pallas`` (None = backend default,
True = the Pallas kernel, False = the jnp reference). ``interpret=True``
with ``use_pallas=True`` — the historical CPU validation spelling — still
means interpret mode.
"""
from typing import Optional

from ...telemetry.recorder import record_kernel_trace
from .autotune import TileConfig, get_tiles
from .kernel import cl_score_channels, ising_cl_logits, target_platform
from .newton import bucket_newton_stats, bucket_newton_stats_ref
from .ref import cl_score_channels_ref, cl_score_ref, ising_cl_logits_ref
from .score import cl_score
from .tiled import bucket_newton_stats_tiled, cl_score_channels_tiled

#: the resolved dispatch paths, as recorded in telemetry ``backend=`` tags.
KERNEL_PATHS = ("mosaic", "tiled", "ref", "interpret")


def default_kernel_path(backend: Optional[str] = None) -> str:
    """The path picked when callers don't force one: compiled everywhere —
    Mosaic on TPU, the XLA-compiled tiled twins elsewhere."""
    backend = backend or target_platform()
    return "mosaic" if backend == "tpu" else "tiled"


def resolve_kernel_path(use_pallas=None, interpret: Optional[bool] = None,
                        backend: Optional[str] = None) -> str:
    """Map the (use_pallas, interpret) caller knobs onto one path name.

    ``use_pallas=None`` → the backend default (:func:`default_kernel_path`);
    ``False`` → ``"ref"``; ``True`` → the Pallas kernel — ``"mosaic"`` where
    it compiles, ``"interpret"`` on CPU or when ``interpret=True`` asks for
    the validation mode explicitly.
    """
    backend = backend or target_platform()
    if use_pallas is None:
        return default_kernel_path(backend)
    if not use_pallas:
        return "ref"
    if interpret or (interpret is None and backend != "tpu"):
        return "interpret"
    return "mosaic"


def _tiles_for(op: str, path: str, *, n: int, p: int, C: int,
               dtype) -> Optional[TileConfig]:
    """Tuned/heuristic tiles for the executing path (None for ref)."""
    if path == "mosaic":
        return get_tiles(op, n=n, p=p, C=C, backend="tpu", dtype=str(dtype))
    if path == "tiled":
        return get_tiles(op, n=n, p=p, C=C, backend="cpu", dtype=str(dtype))
    return None


def conditional_logits_op(x, theta, mask, bias, *, use_pallas=None,
                          interpret: Optional[bool] = None):
    path = resolve_kernel_path(use_pallas, interpret)
    if path == "tiled":
        path = "ref"  # logits have no fused tiled twin; ref IS compiled jnp
    record_kernel_trace("kernel.conditional_logits", backend=path,
                        shape=tuple(x.shape))
    if path == "mosaic":
        return ising_cl_logits(x, theta, mask, bias, interpret=False)
    if path == "interpret":
        return ising_cl_logits(x, theta, mask, bias, interpret=True)
    return ising_cl_logits_ref(x, theta, mask, bias)


def score_stats_op(x, theta, mask, bias, *, kind: str = "ising",
                   use_pallas=None, interpret: Optional[bool] = None):
    """Fused (eta, r, S) pseudo-likelihood score statistics, single-channel.

    ``kind`` selects the family epilogue; every path dispatches through the
    same registry. Safe inside jit — the path choice is a trace-time
    constant.
    """
    path = resolve_kernel_path(use_pallas, interpret)
    record_kernel_trace("kernel.score_stats", kind=kind, backend=path,
                        shape=tuple(x.shape))
    n, p = x.shape
    if path == "mosaic":
        tiles = _tiles_for("score", path, n=n, p=p, C=1, dtype=x.dtype)
        return cl_score(x, theta, mask, bias, kind=kind, interpret=False,
                        tiles=tiles)
    if path == "interpret":
        return cl_score(x, theta, mask, bias, kind=kind, interpret=True)
    if path == "tiled":
        tiles = _tiles_for("score", path, n=n, p=p, C=1, dtype=x.dtype)
        if tiles.bm is not None and tiles.bm < n:
            eta, r, S = cl_score_channels_tiled(
                x[None], theta[None], mask, bias[None], kind=kind,
                chunk=tiles.bm)
            return eta[0], r[0], S[0, 0]
        # whole-axis tiled == the reference contraction, bit-identical
    return cl_score_ref(x, theta, mask, bias, kind=kind)


def score_stats_channels_op(F, theta, mask, bias, *, kind: str,
                            use_pallas=None,
                            interpret: Optional[bool] = None):
    """Channelized fused (eta, r, S) — the multi-channel twin of
    :func:`score_stats_op`."""
    path = resolve_kernel_path(use_pallas, interpret)
    record_kernel_trace("kernel.score_stats_channels", kind=kind,
                        backend=path, shape=tuple(F.shape))
    C, n, p = F.shape
    if path == "mosaic":
        tiles = _tiles_for("score", path, n=n, p=p, C=C, dtype=F.dtype)
        return cl_score_channels(F, theta, mask, bias, kind=kind,
                                 interpret=False, tiles=tiles)
    if path == "interpret":
        return cl_score_channels(F, theta, mask, bias, kind=kind,
                                 interpret=True)
    if path == "tiled":
        tiles = _tiles_for("score", path, n=n, p=p, C=C, dtype=F.dtype)
        if tiles.bm is not None and tiles.bm < n:
            return cl_score_channels_tiled(F, theta, mask, bias, kind=kind,
                                           chunk=tiles.bm)
        # whole-axis tiled == the reference contraction, bit-identical
    return cl_score_channels_ref(F, theta, mask, bias, kind=kind)


def bucket_newton_stats_op(kind, Zb, base, xi, W, sw=None, *,
                           use_pallas=None,
                           interpret: Optional[bool] = None):
    """Fused bucket Newton statistics (g, K), backend-aware.

    Mosaic on TPU (sample tile from the autotuner), the XLA-compiled
    chunked twin on CPU, plain ref / interpret on request.
    Safe to call inside a jit trace — the path choice is a trace-time
    constant.
    """
    path = resolve_kernel_path(use_pallas, interpret)
    record_kernel_trace("kernel.bucket_newton_stats", kind=kind,
                        backend=path, shape=tuple(Zb.shape))
    k, C, d, n = Zb.shape
    if path == "mosaic":
        tiles = _tiles_for("newton", path, n=n, p=d, C=C, dtype=Zb.dtype)
        return bucket_newton_stats(kind, Zb, base, xi, W, sw,
                                   interpret=False, tiles=tiles)
    if path == "interpret":
        return bucket_newton_stats(kind, Zb, base, xi, W, sw,
                                   interpret=True)
    if path == "tiled":
        tiles = _tiles_for("newton", path, n=n, p=d, C=C, dtype=Zb.dtype)
        if tiles.bm is not None and tiles.bm < n:
            return bucket_newton_stats_tiled(kind, Zb, base, xi, W, sw,
                                             chunk=tiles.bm)
        # whole-axis tiled == the reference contraction, bit-identical
    return bucket_newton_stats_ref(kind, Zb, base, xi, W, sw)
