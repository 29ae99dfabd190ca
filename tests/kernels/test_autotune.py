"""Autotuner contract tests: cache determinism, disk round-trip, invalid
tile rejection, and a hypothesis property that tuned tiles never change
results."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.cl import autotune as at
from repro.kernels.cl.autotune import TileConfig


@pytest.fixture(autouse=True)
def _fresh_cache(monkeypatch):
    """Every test sees an empty in-process cache and no env cache file."""
    monkeypatch.delenv("REPRO_CL_TUNE_CACHE", raising=False)
    at.clear_cache()
    yield
    at.clear_cache()


# -------------------------------------------------------------- validation
def test_invalid_tiles_rejected():
    with pytest.raises(ValueError, match="unknown kernel op"):
        at.validate_tile_config(TileConfig(), "matmul")
    with pytest.raises(ValueError, match="TileConfig"):
        at.validate_tile_config({"bm": 128}, "score")
    with pytest.raises(ValueError, match="bm"):
        at.validate_tile_config(TileConfig(bm=0), "score")
    with pytest.raises(ValueError, match="bn"):
        at.validate_tile_config(TileConfig(bn=-8), "score")
    with pytest.raises(ValueError, match="bk"):
        at.validate_tile_config(TileConfig(bk=0), "score")
    # Mosaic (compiled) constraints are stricter
    with pytest.raises(ValueError, match="8-aligned"):
        at.validate_tile_config(TileConfig(bm=None), "score", compiled=True)
    with pytest.raises(ValueError, match="128-multiple"):
        at.validate_tile_config(TileConfig(bm=128, bn=64), "score",
                                compiled=True)
    with pytest.raises(ValueError, match="128-multiple bm"):
        at.validate_tile_config(TileConfig(bm=64), "newton", compiled=True)
    # and valid configs pass through unchanged
    cfg = TileConfig(bm=128, bn=128, bk=128)
    assert at.validate_tile_config(cfg, "newton", compiled=True) is cfg


def test_tile_key_rejects_unknown_op():
    with pytest.raises(ValueError, match="unknown kernel op"):
        at.tile_key("fft", n=10, p=4, C=1)


def test_tile_config_round_trips_dict():
    cfg = TileConfig(bm=512, bn=256, bk=128)
    assert TileConfig.from_dict(cfg.to_dict()) == cfg
    assert TileConfig.from_dict(TileConfig(bm=None).to_dict()).bm is None


# ------------------------------------------------------ cache determinism
def test_search_is_deterministic_and_cached():
    """Two same-key searches: the second never re-measures (empty timings,
    same config) — the acceptance-criterion determinism contract."""
    calls = []

    def measure(cfg):
        calls.append(cfg)
        # deterministic fake cost: prefer the 1024 chunk
        return 1.0 if cfg.bm == 1024 else 2.0

    key = dict(n=50_000, p=9, C=2, backend="cpu")
    best1, t1 = at.search_tiles("newton", measure=measure, **key)
    n_measured = len(calls)
    assert n_measured == len(t1) > 1
    assert best1.bm == 1024
    best2, t2 = at.search_tiles("newton", measure=measure, **key)
    assert best2 == best1
    assert t2 == {}                      # cache hit: no re-search
    assert len(calls) == n_measured      # measure never called again
    # and the trace-time resolver picks the tuned config transparently
    assert at.get_tiles("newton", **key) == best1


def test_search_ties_break_toward_earliest_candidate():
    cands = (TileConfig(bm=None), TileConfig(bm=512), TileConfig(bm=1024))
    best, _ = at.search_tiles("newton", n=40_000, p=5, C=1, backend="cpu",
                              measure=lambda cfg: 1.0, candidates=cands)
    assert best == cands[0]


def test_get_tiles_is_stable_across_calls():
    """Heuristic resolutions are cached: a key resolves once and every
    later lookup returns the identical config (no retrace flip-flop)."""
    a = at.get_tiles("score", n=400, p=20, C=1, backend="cpu")
    b = at.get_tiles("score", n=400, p=20, C=1, backend="cpu")
    assert a == b
    snap = at.cache_snapshot()
    assert at.tile_key("score", n=400, p=20, C=1, backend="cpu") in snap


def test_heuristics_respect_chunk_threshold():
    """Below CHUNK_MIN_N the CPU newton heuristic must be whole-axis (the
    bit-identical reference path the goldens pin)."""
    small = at.get_tiles("newton", n=at.CHUNK_MIN_N - 1, p=5, C=1,
                         backend="cpu")
    assert small.bm is None
    big = at.get_tiles("newton", n=at.CHUNK_MIN_N, p=5, C=1, backend="cpu")
    assert big.bm is not None


# ------------------------------------------------------- disk round-trip
def test_disk_cache_round_trip(tmp_path):
    best, _ = at.search_tiles("newton", n=60_000, p=7, C=1, backend="cpu",
                              measure=lambda cfg: 0.0 if cfg.bm == 2048
                              else 1.0)
    path = str(tmp_path / "tune.json")
    at.save_cache(path)
    payload = json.loads(open(path).read())
    assert payload["version"] == 1

    at.clear_cache()
    assert at.cache_snapshot() == {}
    adopted = at.load_cache(path)
    assert adopted == 1
    assert at.get_tiles("newton", n=60_000, p=7, C=1,
                        backend="cpu") == best
    # in-process entries win over a second load
    assert at.load_cache(path) == 0


def test_load_cache_rejects_unknown_version(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"version": 99, "entries": {}}))
    with pytest.raises(ValueError, match="version"):
        at.load_cache(str(path))


def test_env_cache_loads_lazily_and_persists_searches(tmp_path,
                                                      monkeypatch):
    path = str(tmp_path / "env.json")
    monkeypatch.setenv("REPRO_CL_TUNE_CACHE", path)
    at.clear_cache()
    best, timings = at.search_tiles(
        "newton", n=70_000, p=5, C=1, backend="cpu",
        measure=lambda cfg: 0.0 if cfg.bm == 512 else 1.0)
    assert timings                       # fresh search really measured
    # the search result was appended to the env file ...
    assert json.loads(open(path).read())["entries"]
    # ... and a fresh process (cleared cache) adopts it without searching
    at.clear_cache()
    hit, timings2 = at.search_tiles(
        "newton", n=70_000, p=5, C=1, backend="cpu",
        measure=lambda cfg: pytest.fail("must not re-measure"))
    assert hit == best and timings2 == {}


# --------------------------------------- tuned == default (hypothesis)
@pytest.mark.parametrize("kind", ["ising", "gaussian", "potts"])
def test_tuned_tiles_never_change_results(kind):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.kernels.cl.epilogues import get_epilogue
    from repro.kernels.cl.ref import cl_score_channels_ref
    from repro.kernels.cl.tiled import cl_score_channels_tiled

    ep = get_epilogue(kind)
    C = 2 if ep.channels == "multi" else 1

    @given(n=st.integers(3, 60), p=st.integers(2, 9),
           chunk=st.sampled_from([4, 8, 16, 32]),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=15, deadline=None)
    def prop(n, p, chunk, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        if kind == "potts":
            x = jax.random.randint(ks[0], (n, p), 0, C + 1) \
                .astype(jnp.float32)
        elif kind == "gaussian":
            x = jax.random.normal(ks[0], (n, p))
        else:
            x = jnp.sign(jax.random.normal(ks[0], (n, p)))
        F = ep.features(x, C)
        theta = 0.3 * jax.random.normal(ks[1], (C, p, p))
        mask = jnp.ones((p, p)) - jnp.eye(p)
        bias = 0.1 * jax.random.normal(ks[2], (C, p))
        default = cl_score_channels_ref(F, theta, mask, bias, kind=kind)
        tuned = cl_score_channels_tiled(F, theta, mask, bias, kind=kind,
                                        chunk=chunk)
        for t, d in zip(tuned, default):
            np.testing.assert_allclose(np.asarray(t), np.asarray(d),
                                       atol=1e-6, rtol=1e-6)

    prop()
