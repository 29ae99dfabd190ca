"""A new configuration, traffic mix, load loop, metric and limit are found
by name, with no file of the benchmark edited."""
import hashlib
import io
import json
import time
from pathlib import Path

from bench import harness

LOOP = '''
from bench.harness import Window


class Loop:
    def __init__(self, config, traffic, seed, trace=False):
        self.n = config["n"] * traffic["calls"]

    def setup(self):
        pass

    def window(self, seconds):
        return Window(attempted=self.n, failed=0,
                      end_to_end={"toy_ms": 2.5})

    def telemetry(self):
        return []

    def host_spans(self):
        return []

    def work(self):
        return {}

    def release(self):
        pass

    def check(self):
        return {"toy_gap": 0.0, "not_compared": 5.0}
'''


def _digest(root: Path) -> str:
    h = hashlib.sha256()
    for path in sorted(root.rglob("*")):
        if path.is_file() and "__pycache__" not in path.parts:
            h.update(str(path).encode())
            h.update(path.read_bytes())
    return h.hexdigest()


def _toy_root(tmp: Path) -> Path:
    b = tmp / "bench"
    for sub in ("configs", "traffic", "loops", "metrics", "checks"):
        (b / sub).mkdir(parents=True)
    (b / "configs" / "toy.json").write_text(json.dumps({"n": 3}))
    (b / "traffic" / "toy_mix.json").write_text(
        json.dumps({"loop": "toy_loop", "calls": 4}))
    (b / "loops" / "toy_loop.py").write_text(LOOP)
    (b / "metrics" / "toy.share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (b / "checks" / "toy.cell.json").write_text(json.dumps({"limits": {"toy_gap": 1}}))
    (tmp / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "toy_mix", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "toy_ms", "unit": "ms", "workloads": ["toy.cell"]},
            {"name": "estimate_ms", "unit": "ms",
             "workloads": ["fleet_sf.fit"]}],
        "per_layer": [{"name": "toy.share", "unit": "%",
                       "workloads": ["toy.cell"]}]}))
    return tmp


def test_new_pieces_are_found_by_name(tmp_path):
    before = _digest(harness.BENCH_DIR)
    root = _toy_root(tmp_path)
    layout = harness.Layout(root)
    assert layout.config("toy") == {"n": 3}
    assert layout.traffic("toy_mix")["loop"] == "toy_loop"
    assert layout.metric("toy.share").read(None) == 42.0
    assert layout.limits("toy.cell") == {"toy_gap": 1}
    # the benchmark's own pieces stay reachable beside the new ones
    assert layout.traffic("fit_loop")["verb"] == "fit"
    assert [m["name"] for m in layout.metrics_for("toy.cell",
                                                  "end_to_end")] \
        == ["setup_s", "toy_ms"]

    out = io.StringIO()
    rc = harness.run_cell(layout, "toy.cell", 7, 0.1, False,
                          t_start=time.perf_counter(), require_tpu=False,
                          out=out, err=io.StringIO())
    assert rc == 0
    line = json.loads(out.getvalue().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 12
    assert set(line["metrics"]) == {"setup_s", "toy_ms"}
    assert list(line)[-1] == "checks"
    assert _digest(harness.BENCH_DIR) == before


def test_missing_piece_names_where_it_looked(tmp_path):
    layout = harness.Layout(_toy_root(tmp_path))
    try:
        layout.traffic("no_such_mix")
    except FileNotFoundError as e:
        assert "no_such_mix.json" in str(e)
    else:
        raise AssertionError("a missing traffic mix must raise")


def test_no_tpu_means_no_result_line(tmp_path):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(harness.Layout(_toy_root(tmp_path)), "toy.cell",
                          7, 0.1, False, t_start=time.perf_counter(),
                          out=out, err=err)
    assert rc == harness.NO_DEVICE
    assert out.getvalue() == "" and "TPU" in err.getvalue()


def _run_py(cwd: Path):
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet_sf.fit",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_without_a_tpu_exits_nonzero_with_no_result():
    proc = _run_py(harness.BENCH_DIR.parent)
    assert proc.returncode == harness.NO_DEVICE
    assert proc.stdout == ""


def test_run_py_with_only_the_benchmark_files_fails(tmp_path):
    import shutil
    repo = harness.BENCH_DIR.parent
    shutil.copy(repo / "BENCHMARK.json", tmp_path)
    shutil.copytree(repo / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
