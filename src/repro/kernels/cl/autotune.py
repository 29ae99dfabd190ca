"""Bounded tile-size autotuner for the fused CL kernels.

The fused score kernel (:mod:`.kernel`) and the bucket Newton kernel
(:mod:`.newton`) take a :class:`TileConfig` of trace-time tile sizes; the
compiled-CPU twins (:mod:`.tiled`) take a sample-chunk size. Which tiles
win depends on the operand shape and the backend, so the dispatch layer
(:mod:`.ops`) asks this module instead of hardcoding constants:

* :func:`get_tiles` — the *cheap, deterministic* entry safe to call at jit
  trace time: in-process cache -> optional on-disk JSON cache -> shape
  heuristic. Never times anything, and a given key always resolves to the
  same config within a process (the config is cached on first resolution),
  so repeated traces of one shape compile one program.
* :func:`search_tiles` — the *measured* entry the benchmarks use: times a
  bounded candidate list (:func:`candidate_tiles`) through a caller-provided
  ``measure`` callable and caches the argmin under the same key, so later
  :func:`get_tiles` calls pick the tuned tiles transparently.

Keys are ``(op, backend, dtype, n, p, C)`` — ``op`` is ``"score"`` or
``"newton"``, ``p`` doubles as the bucket design width ``d`` for the
newton op. The cache round-trips through JSON (:func:`save_cache` /
:func:`load_cache`); setting ``REPRO_CL_TUNE_CACHE=/path.json`` loads that
file lazily on first lookup and appends every search result to it.

Search is bounded by construction: candidate lists are a handful of
MXU-aligned configs per (op, backend), every candidate is validated by
:func:`validate_tile_config` before it is timed, and ties break toward the
earliest candidate so two same-key searches agree bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

__all__ = [
    "TileConfig", "KERNEL_OPS", "validate_tile_config", "candidate_tiles",
    "get_tiles", "search_tiles", "save_cache", "load_cache", "clear_cache",
    "cache_snapshot", "tile_key", "CHUNK_MIN_N",
]

#: ops the tuner knows; "score" = the fused (eta, r, S) score pipeline,
#: "newton" = the fused bucket Newton statistics (g, K).
KERNEL_OPS = ("score", "newton")

#: below this many samples the compiled-CPU heuristic never chunks: the
#: whole-axis path is *exactly* the jnp reference contraction (bit-stable
#: with the 1e-10 golden fixtures), and measured chunking only wins once
#: the sample axis outgrows cache (see BENCH_kernels.json newton rows).
CHUNK_MIN_N = 16384

_ENV_CACHE = "REPRO_CL_TUNE_CACHE"


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One tile assignment, hashable so it rides as a static jit argument.

    bm : sample-axis tile. For the Pallas kernels this is the per-grid-step
        sample block; for the compiled-CPU twins it is the scan chunk.
        ``None`` means "whole axis" — no chunking, reference contraction
        order.
    bn : output-column tile of the score kernel (ignored by newton).
    bk : contraction tile of the score kernel (ignored by newton).
    """

    bm: Optional[int] = 128
    bn: int = 128
    bk: int = 128

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TileConfig":
        return cls(bm=d.get("bm"), bn=int(d.get("bn", 128)),
                   bk=int(d.get("bk", 128)))


def validate_tile_config(cfg: TileConfig, op: str,
                         compiled: bool = False) -> TileConfig:
    """Reject tile configs the kernels cannot run; returns ``cfg``.

    ``compiled=True`` applies the Mosaic (real-TPU) constraints on top of
    the structural ones: 128-multiple score lane tiles and an explicit
    (8-aligned) sample tile, 128-aligned for newton, whose blocks carry
    the sample axis on the lanes. Interpret mode and the compiled-CPU
    twins only need positive sizes.
    """
    if op not in KERNEL_OPS:
        raise ValueError(f"unknown kernel op {op!r}; choose from "
                         f"{KERNEL_OPS}")
    if not isinstance(cfg, TileConfig):
        raise ValueError(f"expected a TileConfig, got "
                         f"{type(cfg).__name__}")
    if cfg.bm is not None and (not isinstance(cfg.bm, int) or cfg.bm < 1):
        raise ValueError(f"bm must be a positive int or None, got "
                         f"{cfg.bm!r}")
    for name, v in (("bn", cfg.bn), ("bk", cfg.bk)):
        if not isinstance(v, int) or v < 1:
            raise ValueError(f"{name} must be a positive int, got {v!r}")
    if compiled:
        if cfg.bm is None or cfg.bm % 8:
            raise ValueError(
                f"compiled Pallas path needs an explicit 8-aligned sample "
                f"tile, got bm={cfg.bm!r}")
        if op == "newton" and cfg.bm % 128:
            raise ValueError(
                f"compiled newton kernel tiles the sample axis on the "
                f"lanes and needs a 128-multiple bm, got bm={cfg.bm!r}")
        if op == "score" and (cfg.bn % 128 or cfg.bk % 128):
            raise ValueError(
                f"compiled score kernel needs 128-multiple lane tiles, got "
                f"bn={cfg.bn} bk={cfg.bk}")
    return cfg


def tile_key(op: str, *, n: int, p: int, C: int,
             backend: Optional[str] = None, dtype: str = "float32") -> str:
    """The canonical cache key string for one (op, shape, backend, dtype)."""
    if op not in KERNEL_OPS:
        raise ValueError(f"unknown kernel op {op!r}; choose from "
                         f"{KERNEL_OPS}")
    backend = backend or jax.default_backend()
    return f"{op}|{backend}|{dtype}|n={int(n)}|p={int(p)}|C={int(C)}"


# ------------------------------------------------------------------ caches
_LOCK = threading.Lock()
_CACHE: Dict[str, TileConfig] = {}
_ENV_LOADED = False


def clear_cache() -> None:
    """Drop every in-process entry (and forget the lazy env-file load)."""
    global _ENV_LOADED
    with _LOCK:
        _CACHE.clear()
        _ENV_LOADED = False


def cache_snapshot() -> Dict[str, TileConfig]:
    """A copy of the current in-process cache (for tests / diagnostics)."""
    with _LOCK:
        return dict(_CACHE)


def save_cache(path: str) -> str:
    """Write the in-process cache to ``path`` as JSON; returns ``path``."""
    with _LOCK:
        payload = {"version": 1,
                   "entries": {k: v.to_dict() for k, v in _CACHE.items()}}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_cache(path: str) -> int:
    """Merge a :func:`save_cache` file into the in-process cache.

    Existing in-process entries win (they may be fresher searches). Returns
    the number of entries adopted from disk. Unknown file versions raise —
    a layout this reader predates must not silently misconfigure kernels.
    """
    with open(path) as f:
        payload = json.load(f)
    version = payload.get("version")
    if version != 1:
        raise ValueError(f"{path}: tune-cache version {version!r} unknown "
                         f"to this reader (understands 1)")
    adopted = 0
    with _LOCK:
        for key, d in payload.get("entries", {}).items():
            if key not in _CACHE:
                _CACHE[key] = TileConfig.from_dict(d)
                adopted += 1
    return adopted


def _load_env_cache() -> None:
    global _ENV_LOADED
    if _ENV_LOADED:
        return
    _ENV_LOADED = True
    path = os.environ.get(_ENV_CACHE)
    if path and os.path.exists(path):
        try:
            load_cache(path)
        except (ValueError, OSError, json.JSONDecodeError):
            pass  # a corrupt cache must never break dispatch


# -------------------------------------------------------------- heuristics
def _default_tiles(op: str, *, n: int, p: int, C: int,
                   backend: str) -> TileConfig:
    """Shape heuristic used when nothing tuned is cached. Deterministic.

    TPU: MXU-aligned 128s everywhere.
    CPU: the compiled-CPU twins — whole-axis (== the jnp reference
    contraction, golden-bit-stable) below :data:`CHUNK_MIN_N` samples,
    cache-sized 1024-sample chunks above it.
    """
    if backend == "tpu":
        return TileConfig(bm=128, bn=128, bk=128)
    if op == "newton" and n >= CHUNK_MIN_N:
        return TileConfig(bm=1024)
    return TileConfig(bm=None)


def candidate_tiles(op: str, *, n: int, p: int, C: int,
                    backend: Optional[str] = None) -> Tuple[TileConfig, ...]:
    """The bounded search space for one key, heuristic default first.

    Small by design — the tuner is a measured tiebreak between a handful of
    MXU-aligned configs, not a general scheduler. Candidates whose chunk
    would exceed the sample axis are dropped (they alias the whole-axis
    config).
    """
    backend = backend or jax.default_backend()
    default = _default_tiles(op, n=n, p=p, C=C, backend=backend)
    if backend == "tpu":
        if op == "newton":
            cands = [default] + [TileConfig(bm=bm) for bm in (256, 512)]
        else:
            cands = [default,
                     TileConfig(bm=256, bn=128, bk=128),
                     TileConfig(bm=128, bn=256, bk=128),
                     TileConfig(bm=512, bn=128, bk=128)]
    else:
        chunks = (None, 512, 1024, 2048) if op == "newton" \
            else (None, 1024, 4096)
        cands = [TileConfig(bm=c) for c in chunks
                 if c is None or c < n]
        if default not in cands:
            cands.insert(0, default)
    seen, out = set(), []
    for c in cands:
        if c not in seen:
            seen.add(c)
            out.append(validate_tile_config(c, op,
                                            compiled=backend == "tpu"))
    return tuple(out)


# ----------------------------------------------------------------- entries
def get_tiles(op: str, *, n: int, p: int, C: int,
              backend: Optional[str] = None,
              dtype: str = "float32") -> TileConfig:
    """Resolve tiles for one key without measuring anything.

    Lookup order: in-process cache -> ``REPRO_CL_TUNE_CACHE`` JSON (loaded
    once, lazily) -> shape heuristic. The resolution is cached, so the
    same key always returns the same config for the life of the process —
    jit traces of one shape can never flip tiles between retraces.
    """
    backend = backend or jax.default_backend()
    key = tile_key(op, n=n, p=p, C=C, backend=backend, dtype=dtype)
    with _LOCK:
        hit = _CACHE.get(key)
    if hit is not None:
        return hit
    _load_env_cache()
    with _LOCK:
        hit = _CACHE.get(key)
        if hit is None:
            hit = _default_tiles(op, n=n, p=p, C=C, backend=backend)
            _CACHE[key] = hit
    return hit


def search_tiles(op: str, *, n: int, p: int, C: int,
                 measure: Callable[[TileConfig], float],
                 backend: Optional[str] = None, dtype: str = "float32",
                 candidates: Optional[Sequence[TileConfig]] = None,
                 ) -> Tuple[TileConfig, Dict[str, float]]:
    """Measured tile search; returns ``(best, timings)``.

    ``measure(cfg)`` runs the kernel under ``cfg`` and returns a cost
    (seconds or any monotone proxy). The argmin — ties break toward the
    earliest candidate, so same-key searches are deterministic — is cached
    under the key, after which :func:`get_tiles` (and therefore the
    :mod:`.ops` dispatch layer) picks it transparently. A key already in
    the cache is returned as-is with empty ``timings`` — **no re-search** —
    which is what makes two same-key runs cheap and identical; call
    :func:`clear_cache` to force a fresh search.

    With ``REPRO_CL_TUNE_CACHE`` set, every fresh search result is appended
    to that JSON file so later processes skip the search too.
    """
    backend = backend or jax.default_backend()
    key = tile_key(op, n=n, p=p, C=C, backend=backend, dtype=dtype)
    _load_env_cache()
    with _LOCK:
        hit = _CACHE.get(key)
    if hit is not None:
        return hit, {}
    cands = tuple(candidates) if candidates is not None else \
        candidate_tiles(op, n=n, p=p, C=C, backend=backend)
    if not cands:
        raise ValueError(f"no tile candidates for key {key!r}")
    compiled = backend == "tpu"
    timings: Dict[str, float] = {}
    best, best_cost = None, None
    for cfg in cands:
        validate_tile_config(cfg, op, compiled=compiled)
        cost = float(measure(cfg))
        timings[repr(cfg)] = cost
        if best_cost is None or cost < best_cost:
            best, best_cost = cfg, cost
    with _LOCK:
        _CACHE[key] = best
    path = os.environ.get(_ENV_CACHE)
    if path:
        try:
            save_cache(path)
        except OSError:
            pass
    return best, timings
