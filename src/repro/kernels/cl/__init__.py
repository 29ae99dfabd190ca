"""Family-generic fused conditional-likelihood (CL) kernel subsystem.

One channelized Pallas pipeline — load -> eta -> residual -> score/Gram
epilogue — shared by every registered exponential family, with the
per-family math isolated in a small :mod:`~repro.kernels.cl.epilogues`
registry keyed by ``ModelFamily.kernel_kind``:

* :mod:`.kernel` — the pallas_call skeleton (masked-matmul logits kernel and
  the channelized fused score kernel), tile-parametric with
  divisibility-safe edge padding;
* :mod:`.epilogues` — the epilogue registry (ising / gaussian / potts ship);
* :mod:`.ref` — pure-jnp oracles for everything;
* :mod:`.newton` — the fused Newton-step entry point emitting score + Gram
  directly in the degree-bucket ``(k, C, d)`` layout ``core/batched.py``
  consumes;
* :mod:`.tiled` — XLA-compiled CPU twins of the fused kernels (Pallas is
  interpret-only on CPU); the compiled-CPU dispatch tier;
* :mod:`.autotune` — bounded tile-size search keyed by
  ``(op, backend, dtype, n, p, C)`` with deterministic in-process and
  on-disk JSON caches;
* :mod:`.precision` — the documented per-``Plan.precision`` conformance
  tolerances (float64 / float32 / mixed-precision bfloat16);
* :mod:`.score` — seed-compatible single-channel entry points
  (``cl_score``, ``ising_cl_score``, padded-buffer variants) plus the
  channelized ``cl_score_channels``;
* :mod:`.family` — adapters from a :class:`ModelFamily` + graph + flat theta
  to kernel inputs, and the fused flat pseudo-score the streaming stack
  uses;
* :mod:`.ops` — backend-aware dispatch (Mosaic on TPU, the compiled
  tiled twins on CPU, ref / interpret on request) with tuned tiles and
  trace-time telemetry tags.

The old ``repro.kernels.ising_cl`` package remains as import shims.
"""
from .autotune import (CHUNK_MIN_N, KERNEL_OPS, TileConfig, cache_snapshot,
                       candidate_tiles, clear_cache, get_tiles, load_cache,
                       save_cache, search_tiles, tile_key,
                       validate_tile_config)
from .epilogues import (Epilogue, get_epilogue, register_epilogue,
                        registered_kinds)
from .kernel import cl_logits, cl_score_channels, ising_cl_logits
from .newton import bucket_newton_stats, bucket_newton_stats_ref
from .ops import (KERNEL_PATHS, bucket_newton_stats_op, conditional_logits_op,
                  default_kernel_path, resolve_kernel_path,
                  score_stats_channels_op, score_stats_op)
from .precision import PRECISION_TOLERANCES, precision_tolerance
from .ref import (cl_logits_ref, cl_score_channels_ref, cl_score_ref,
                  ising_cl_logits_ref, ising_cl_score_ref)
from .score import (KERNEL_KINDS, cl_score, cl_score_channels_padded,
                    cl_score_padded, ising_cl_score, ising_cl_score_padded)
from .tiled import bucket_newton_stats_tiled, cl_score_channels_tiled
from .family import family_kernel_inputs, family_score_stats, fused_pseudo_score

__all__ = [
    "Epilogue", "register_epilogue", "get_epilogue", "registered_kinds",
    "cl_logits", "ising_cl_logits", "cl_score_channels",
    "cl_score", "cl_score_padded", "cl_score_channels_padded",
    "ising_cl_score", "ising_cl_score_padded", "KERNEL_KINDS",
    "cl_score_ref", "cl_score_channels_ref", "cl_logits_ref",
    "ising_cl_logits_ref", "ising_cl_score_ref",
    "bucket_newton_stats", "bucket_newton_stats_ref",
    "cl_score_channels_tiled", "bucket_newton_stats_tiled",
    "conditional_logits_op", "score_stats_op", "score_stats_channels_op",
    "bucket_newton_stats_op", "KERNEL_PATHS", "default_kernel_path",
    "resolve_kernel_path",
    "TileConfig", "KERNEL_OPS", "CHUNK_MIN_N", "get_tiles", "search_tiles",
    "candidate_tiles", "validate_tile_config", "tile_key", "save_cache",
    "load_cache", "clear_cache", "cache_snapshot",
    "PRECISION_TOLERANCES", "precision_tolerance",
    "family_kernel_inputs", "family_score_stats", "fused_pseudo_score",
]
