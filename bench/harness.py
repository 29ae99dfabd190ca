"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The harness is driven by data. ``BENCHMARK.json`` names the cell's
configuration and traffic mix; each piece is a file found by its name:

* ``<file>`` of the configuration (``bench/configs/<config>.json``):
  graph, family, sizes, combiners;
* ``bench/traffic/<mix>.json``: the parameters of a traffic mix, and the
  name of the general load loop that reads them;
* ``bench/loops/<loop>.py``: a ``Loop`` class (set-up, measured
  window, and ``check()``: the numbers that compare what the window
  produced with the plain reference);
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` function per per-layer
  metric, returning a number or None when it finds nothing to read;
* ``bench/checks/<cell>.json``: the limit of each number the correctness
  check compares.

A later cell, mix or metric is a new file and a new ``BENCHMARK.json``
entry; no file here changes. Each name is first looked up under the
checkout's own ``bench/`` directory, then beside this file.

A run warms up every shape of its cell (set-up, timed from process
start), measures for ``--seconds`` with nothing compiling, reads device
memory, frees the program's state, then compares what the window produced
with the float64 reference. ``--trace 1`` turns on the program's
telemetry and a profiler trace of the window, and reports the per-layer
metrics instead of the end-to-end ones, over a window of at most
``TRACE_SECONDS``. Without a TPU (or with fewer
chips than the cell asks for) it prints no result and exits 3.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
NO_DEVICE = 3
#: a traced run measures at most this long: a longer trace is hundreds of
#: MB of device events and slow to read, and every per-layer metric is a
#: share or a mean per call, which a shorter window reads alike
TRACE_SECONDS = 10.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------ discovery
class Layout:
    """Finds the benchmark's pieces by name under a checkout root."""

    def __init__(self, root: Path, fallback: Path = BENCH_DIR) -> None:
        self.root = Path(root)
        self.dirs = [self.root / "bench"]
        if fallback.resolve() not in [d.resolve() for d in self.dirs
                                      if d.exists()]:
            self.dirs.append(fallback)

    def benchmark(self) -> dict:
        return json.loads((self.root / "BENCHMARK.json").read_text())

    def _find(self, kind: str, name: str, suffix: str) -> Path:
        for d in self.dirs:
            path = d / kind / f"{name}{suffix}"
            if path.is_file():
                return path
        raise FileNotFoundError(
            f"no {kind[:-1] if kind.endswith('s') else kind} named "
            f"{name!r}: looked for {name}{suffix} under "
            f"{[str(d / kind) for d in self.dirs]}")

    def cell(self, name: str) -> dict:
        for w in self.benchmark()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.benchmark()["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(self._find("traffic", name, ".json").read_text())

    def checks(self, cell: str) -> dict:
        """``{"limits": {number: limit}, "control": precision}``: the
        limit of each compared number, and the control it was set
        against."""
        return json.loads(self._find("checks", cell, ".json").read_text())

    def limits(self, cell: str) -> dict:
        return self.checks(cell)["limits"]

    def _module(self, kind: str, name: str):
        path = self._find(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def loop(self, name: str):
        return self._module("loops", name)

    def metric(self, name: str):
        return self._module("metrics", name)

    def metrics_for(self, cell: str, kind: str) -> List[dict]:
        """The ``kind`` ("end_to_end" / "per_layer") metrics a cell
        reports: those that list it, or that list no cells."""
        return [m for m in self.benchmark()[kind]
                if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------ run state
class CompileCounter:
    """XLA backend compilations of this process."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            self.n += 1


@dataclasses.dataclass
class Window:
    """What a load loop's measured window returns."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: per-request readings for the per-layer metrics (not printed)
    samples: Dict[str, list] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    """Everything a per-layer metric reader may read."""
    cell: dict
    config: dict
    traffic: dict
    window: Window
    trace: Optional[Any]            # bench.trace.Reduction
    telemetry: List[Any]            # program TelemetrySnapshots, one a call
    work: Dict[str, Any]            # shapes for the required-work counts
    peak: Optional[dict]


def _device_info(devices, chips: int) -> dict:
    dev = devices[0]
    peak = 0
    for d in devices[:chips]:
        try:
            stats = d.memory_stats() or {}
        except Exception:      # noqa: BLE001 - a backend without stats
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips, "memory_peak_bytes": peak}


def _json_number(v: float) -> float:
    return float(v) if math.isfinite(v) else 1e300


@contextlib.contextmanager
def _profiled(trace_dir: Optional[str], clock: dict):
    """Profile the block (Python tracing off) inside a ``bench_window``
    annotation; ``clock["start"]`` gets the perf_counter as it opens."""
    if trace_dir is None:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        clock["start"] = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_window"):
            yield
    finally:
        jax.profiler.stop_trace()


def run_cell(layout: Layout, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             out=sys.stdout, err=sys.stderr) -> int:
    cell = layout.cell(workload)
    config = layout.config(cell["config"])
    traffic = layout.traffic(cell["traffic"])
    limits = layout.limits(workload)
    chips = int(cell["chips"])

    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        print(f"bench: cell {workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=err)
        return NO_DEVICE

    counter = CompileCounter()
    loop = layout.loop(traffic["loop"]).Loop(
        config, traffic, seed, trace=trace)
    loop.setup()
    setup_s = time.perf_counter() - t_start
    c0 = counter.n
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    clock: Dict[str, float] = {}
    try:
        with _profiled(trace_dir, clock):
            window = loop.window(min(seconds, TRACE_SECONDS) if trace
                                   else seconds)
        window_compiles = counter.n - c0
        device = _device_info(devices, chips)
        metrics: Dict[str, dict] = {}
        breakdown = None
        if not trace:
            values = dict(window.end_to_end, setup_s=setup_s)
            for m in layout.metrics_for(workload, "end_to_end"):
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        else:
            from bench.trace import reduce_trace
            from bench.work import peaks
            red = reduce_trace(trace_dir, n_devices=chips,
                               host_spans=loop.host_spans(),
                               window_start=clock["start"])
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            breakdown = red.breakdown()
            ctx = Context(cell=cell, config=config, traffic=traffic,
                          window=window, trace=red,
                          telemetry=loop.telemetry(),
                          work=loop.work(),
                          peak=peaks(device["kind"])
                          if device["platform"] == "tpu" else None)
            for m in layout.metrics_for(workload, "per_layer"):
                v = layout.metric(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    loop.release()
    t_ref = time.perf_counter()
    readings = loop.check()
    ref_s = time.perf_counter() - t_ref
    # a number the check could not read is a failed comparison
    checks = {name: {"value": float(readings.get(name, math.inf)),
                     "limit": float(limit)}
              for name, limit in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and bool(checks)
    print(f"bench: window_compiles={window_compiles} reference_s={ref_s:.3f} "
          f"stats={json.dumps(window.stats)}", file=err)
    print(f"bench: readings={json.dumps(readings)}", file=err)
    for name, c in checks.items():
        print(f"{name} {float(c['value'])!r} <= {float(c['limit'])!r}",
              file=err)
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed,
              "metrics": {k: {"value": _json_number(v["value"]),
                              "unit": v["unit"]} for k, v in metrics.items()},
              "device": device, "window_compiles": window_compiles,
              "reference_s": ref_s}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": _json_number(c["value"]),
                            "limit": c["limit"]} for k, c in checks.items()}
    print(json.dumps(result), file=out, flush=True)
    return 0


def enable_cache(root: Path) -> None:
    """JAX's persistent compile cache at ``<root>/.jax_cache``, one fixed
    place per checkout, holding every program however fast it compiled.
    The program's own hook takes the directory from the environment."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    enable_cache(Path.cwd())
    return run_cell(Layout(Path.cwd()), args.workload, args.seed,
                    args.seconds, bool(args.trace), t_start=t_start)
