"""Stable device-side names: the jitted bucket solves and the score run
under ``jax.named_scope`` blocks that name their layer, and each
``pallas_call`` carries a fixed ``name``, so a profiler trace's operation
metadata keys on them whatever the surrounding code is called. The XLA
module names (``jit__solve_bucket``, ``jit_cl_score_channels``) that the
benchmark's rooflines read stay as they were.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import batched
from repro.core.families import ISING
from repro.kernels.cl.kernel import cl_logits, cl_score_channels
from repro.kernels.cl.newton import bucket_newton_stats
from repro.kernels.cl.tiled import cl_score_channels_tiled

N, P, K, DEG = 32, 6, 3, 2


def _s(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _bucket_args():
    return (_s(N, P), _s(K, dtype=jnp.int32), _s(K, DEG, dtype=jnp.int32),
            _s(K, DEG), _s(K, 1), _s(K, DEG + 1), _s(1, 1))


def _score_args():
    return _s(1, N, P), _s(1, P, P), _s(P, P), _s(1, P)


CASES = {
    "bucket_solve": lambda: batched._solve_bucket.lower(
        *_bucket_args(), include_singleton=True, n_iter=3, weighted=False,
        guarded=False, family=ISING, want_influence=True),
    "prox_bucket_solve": lambda: batched._solve_bucket_prox.lower(
        *_bucket_args(), _s(K, DEG + 1), _s(K, DEG + 1), _s(K, DEG + 1),
        include_singleton=True, n_iter=3, weighted=False, family=ISING),
    "score": lambda: cl_score_channels.lower(
        *_score_args(), kind="ising", interpret=True),
    "score_tiled": lambda: cl_score_channels_tiled.lower(
        *_score_args(), kind="ising", chunk=8),
    "logits": lambda: cl_logits.lower(*_score_args(), interpret=True),
    "newton": lambda: bucket_newton_stats.lower(
        "ising", _s(K, 1, DEG + 1, N), _s(K, 1, N), _s(K, N),
        _s(K, DEG + 1), interpret=True),
}

#: case -> (XLA module, a scope path that the operation metadata must hold)
EXPECT = {
    "bucket_solve": ("jit__solve_bucket", "jit(_solve_bucket)/bucket_solve/"),
    "prox_bucket_solve": ("jit__solve_bucket_prox",
                          "jit(_solve_bucket_prox)/prox_bucket_solve/"),
    "score": ("jit_cl_score_channels",
              "jit(cl_score_channels)/score/cl_score_channels/"),
    "score_tiled": ("jit_cl_score_channels_tiled",
                    "jit(cl_score_channels_tiled)/score/"),
    "logits": ("jit_cl_logits", "jit(cl_logits)/cl_logits/pallas_call"),
    "newton": ("jit_bucket_newton_stats",
               "jit(bucket_newton_stats)/bucket_newton_stats/pallas_call"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_lowered_metadata_carries_the_names(case):
    lowered = CASES[case]()
    module, scope = EXPECT[case]
    assert lowered.as_text().startswith(f"module @{module} ")
    assert scope in lowered.as_text(debug_info=True)


def test_bucket_solve_scope_reaches_the_compiled_hlo():
    compiled = CASES["bucket_solve"]().compile().as_text()
    assert compiled.startswith("HloModule jit__solve_bucket,")
    assert 'op_name="jit(_solve_bucket)/bucket_solve/' in compiled
