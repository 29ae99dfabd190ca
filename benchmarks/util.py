"""Benchmark harness utilities: timing + CSV/JSON emission.

Every benchmark prints ``name,us_per_call,derived`` rows so ``benchmarks.run``
output is machine-parsable. ``derived`` is the figure's scientific payload
(efficiency, MSE, ...) as a compact string. Benchmarks that track a perf
trajectory additionally write a ``BENCH_*.json`` file at the repo root via
:func:`emit_json`.
"""
from __future__ import annotations

import datetime
import json
import os
import subprocess
import time
from contextlib import contextmanager

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: current BENCH_*.json schema. v1 = the pre-provenance payloads (no
#: version stamp at all); v2 adds the top-level ``schema_version`` +
#: ``provenance`` block. tools.gen_tables refuses versions it does not
#: know, so a reader never silently misrenders a newer layout.
BENCH_SCHEMA_VERSION = 2


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except Exception:
        return "unknown"


def provenance() -> dict:
    """Where/when/how this benchmark ran: git SHA (``"unknown"`` outside a
    work tree), UTC timestamp, jax backend, and the default kernel path
    the dispatch layer picks on this backend (the
    :data:`repro.kernels.cl.ops.KERNEL_PATHS` taxonomy — Mosaic Pallas on
    TPU, the XLA tiled twin elsewhere)."""
    import jax
    from repro.kernels.cl.ops import default_kernel_path
    backend = jax.default_backend()
    return {
        "git_sha": _git_sha(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "backend": backend,
        "kernel_path": default_kernel_path(backend),
    }


def emit_json(filename: str, payload: dict) -> str:
    """Write a machine-readable benchmark record to the repo root.

    Every record is stamped with ``schema_version`` and a ``provenance``
    block (git SHA, UTC timestamp, backend/kernel mode) before writing —
    a BENCH file is meaningless as evidence without knowing what produced
    it. Writers may pre-set either key to override."""
    payload.setdefault("schema_version", BENCH_SCHEMA_VERSION)
    payload.setdefault("provenance", provenance())
    path = os.path.join(REPO_ROOT, filename)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}")
    return path


@contextmanager
def timed(holder: dict, key: str = "t"):
    t0 = time.perf_counter()
    yield
    holder[key] = (time.perf_counter() - t0) * 1e6  # microseconds


def scale(quick_val, full_val):
    """Pick a problem size depending on REPRO_BENCH_FULL."""
    return full_val if FULL else quick_val
