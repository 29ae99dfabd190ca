"""Shared body of the per-cell control and fault tests."""
import json
from pathlib import Path

from bench.harness import Layout
from bench.tests.tiny import REPO, run_tiny, tiny_root

SEED = 2**33 + 77


def control_readings(tmp: Path, workload: str):
    """(readings of a sound run, readings with the control in the
    program's place, the limits)."""
    root = tiny_root(tmp)
    layout = Layout(root)
    cell = layout.cell(workload)
    traffic = layout.traffic(cell["traffic"])
    spec = json.loads((REPO / "bench" / "checks" /
                       f"{workload}.json").read_text())
    drv = layout.loop(traffic["loop"]).Loop(
        layout.config(cell["config"]), traffic, SEED)
    drv.setup()
    drv.window(0.3)
    drv.release()
    sound = drv.check()
    drv.control_answers(spec["control"])
    return sound, drv.check(), spec["limits"]


def assert_control_fails(tmp: Path, workload: str):
    sound, control, limits = control_readings(tmp, workload)
    assert all(sound[k] <= v for k, v in limits.items()), (sound, limits)
    assert any(control[k] > v for k, v in limits.items()), (control,
                                                            limits)


def assert_fault_fails(tmp: Path, workload: str, fault):
    root = tiny_root(tmp)
    with fault():
        line = run_tiny(root, workload, seed=SEED, seconds=0.3)
    assert line["correct"] is False, line["checks"]
    assert list(line)[-1] == "checks"
