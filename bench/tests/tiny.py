"""A checkout root whose configurations are cut to CPU-test size.

The traffic mixes, load loops, metrics and limits are the real ones (found
beside the harness), and so are the cells, the metrics and the
configurations, except that each configuration is cut to a small copy:
a 20-node fleet at n = 400 and a 6 x 6 lattice at n = 512. Every cell
built here is listed, whether or not ``BENCHMARK.json`` measures it yet.
"""
import io
import json
import time
from contextlib import redirect_stderr
from pathlib import Path

from bench.harness import BENCH_DIR, Layout, run_cell

REPO = BENCH_DIR.parent
SMALL = {"barabasi_albert": dict(p=20, n=400),
         "grid": dict(rows=6, cols=6, p=36, n=512)}


CELLS = {"fleet_sf.fit": ("fleet_sf", "fit_loop"),
         "lattice64.fit": ("lattice64", "fit_loop"),
         "fleet_sf.serve": ("fleet_sf", "serve_open"),
         "fleet_sf.joint": ("fleet_sf", "joint_loop")}


def tiny_root(tmp: Path) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    bench["configs"] = []
    for name in sorted({c for c, _ in CELLS.values()}):
        cfg = json.loads((BENCH_DIR / "configs" / f"{name}.json")
                         .read_text())
        cfg.update(SMALL[cfg["graph"]], gibbs_sweeps=50)
        path = f"bench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "file": path})
    bench["workloads"] = [{"name": w, "config": c, "traffic": t,
                           "chips": 1} for w, (c, t) in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_tiny(root: Path, workload: str, seed: int = 2**33 + 1,
             seconds: float = 0.5, trace: bool = False) -> dict:
    """Drive a whole run of ``workload`` on the CPU; its result line."""
    out, err = io.StringIO(), io.StringIO()
    with redirect_stderr(err):
        rc = run_cell(Layout(root), workload, seed, seconds, trace,
                      t_start=time.perf_counter(), require_tpu=False,
                      out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])
