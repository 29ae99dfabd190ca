"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so the Mosaic lowering's refusals (block shapes
off the (8, 128) tiling, vectors below 2-D, too much VMEM) show here at
no chip time. Shapes are the deployment widths: the paper's 100-node fleet
(a k = 64 degree bucket of d = 5 coordinates, n = 4096 samples) and a
32x32 lattice (p = 1024, one k = 1024 bucket).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batched
from repro.core.families import get_family
from repro.kernels.cl import ops
from repro.kernels.cl.autotune import get_tiles
from repro.kernels.cl.kernel import cl_score_channels
from repro.kernels.cl.newton import bucket_newton_stats

N = 4096
FLEET_K, FLEET_P, LATTICE_P, D = 64, 100, 1024, 5


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kind, weighted, k", [
    ("ising", False, FLEET_K),
    ("gaussian", False, FLEET_K),
    ("potts", False, FLEET_K),
    ("ising", True, FLEET_K),
    ("ising", False, LATTICE_P),
])
def test_bucket_newton_stats_compiles_for_v5e(one_chip, kind, weighted, k):
    C = get_family(kind).block_dim
    tiles = get_tiles("newton", n=N, p=D, C=C, backend="tpu")

    def fn(Zb, base, xi, W, sw):
        return bucket_newton_stats(kind, Zb, base, xi, W,
                                   sw if weighted else None,
                                   interpret=False, tiles=tiles)

    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    compiled = _compile(fn, s(k, C, D, N), s(k, C, N), s(k, N),
                        s(k, D * C), s(k, N))
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= 4 * k * (D * C + (D * C) ** 2)


@pytest.mark.parametrize("kind, p", [
    ("ising", FLEET_P), ("potts", FLEET_P), ("ising", LATTICE_P)])
def test_cl_score_channels_compiles_for_v5e(one_chip, kind, p):
    C = get_family(kind).block_dim
    tiles = get_tiles("score", n=N, p=p, C=C, backend="tpu")

    def fn(F, theta, mask, bias):
        return cl_score_channels(F, theta, mask, bias, kind=kind,
                                 interpret=False, tiles=tiles)

    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    _compile(fn, s(C, N, p), s(C, p, p), s(p, p), s(C, p))


def test_bucket_solver_compiles_with_mosaic_kernel(one_chip, monkeypatch):
    """The whole jitted bucket solve (Newton while-loop, Gauss-Jordan
    sweeps, sandwich) with the kernel dispatch steered to the TPU path,
    at the lattice bucket: one program that fits a v5e's 16 GB."""
    monkeypatch.setattr(ops, "target_platform", lambda: "tpu")
    k, deg = LATTICE_P, 4
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa
    args = (s((N, LATTICE_P)), s((k,), jnp.int32), s((k, deg), jnp.int32),
            s((k, deg)), s((k, 1)), s((k, deg + 1)), s((1, 1)))
    compiled = batched._solve_bucket.lower(
        *args, include_singleton=True, n_iter=40, weighted=False,
        guarded=False, family=get_family("ising"),
        want_influence=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert np.isfinite(total) and total < 16e9
