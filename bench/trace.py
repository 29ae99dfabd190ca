"""Reduce a profiler trace of the measured window to numbers.

The harness records the window with ``jax.profiler`` (Python tracing off)
inside a host annotation named ``bench_window``. From the ``.xplane.pb``
this gives:

* ``window_s``: the annotation's length;
* ``busy_s``: the union of the device's operation intervals (the
  ``XLA Ops`` line of each ``/device:`` plane) inside the window, averaged
  over the devices;
* ``module_s``: device operation seconds per XLA module (the ``XLA
  Modules`` line; ``jit__solve_bucket(123)`` counts as
  ``jit__solve_bucket``), an operation belonging to the module whose
  execution interval holds its start;
* ``op_s``: device seconds per ``module:operation``;
* the idle gaps between device operations, each labelled by the host
  activity around its middle: the innermost of the labelled host
  intervals a load loop supplies (the program's telemetry spans, the
  benchmark's own phases), else the innermost host annotation of the
  trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
_MODULE_ID = re.compile(r"\(\d+\)$")
_OP_NAME = re.compile(r"^%?([^\s=]+)")


def find_xplane(path: str) -> str:
    """The ``.xplane.pb`` under a trace directory (or the file itself)."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def module_name(event_name: str) -> str:
    return _MODULE_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Complement of the intervals' union inside [lo, hi]."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    n_devices: int
    module_s: Dict[str, float]
    op_s: Dict[str, float]
    idle: List[Tuple[str, float]]          # (label, seconds), longest first

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def module_seconds(self, name: str) -> float:
        return self.module_s.get(name, 0.0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle[:top]]}


def _self_seconds(ops):
    """Seconds of each (op key, module) not covered by an op nested in it
    (a ``while`` op holds its body's ops on the same line)."""
    out: Dict[Tuple[str, str], float] = {}
    stack: List[list] = []              # [end, key, mod, self_ns]

    def close(entry):
        k = (entry[1], entry[2])
        out[k] = out.get(k, 0.0) + max(entry[3], 0.0) * 1e-9

    for a, b, key, mod in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] -= min(b, stack[-1][0]) - a
        stack.append([b, key, mod, b - a])
    while stack:
        close(stack.pop())
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce_trace(path: str, n_devices: int = 1,
                 host_spans: Optional[Sequence[Tuple[float, float, str]]]
                 = None, window_start: float = 0.0) -> Reduction:
    """Reduce the trace at ``path`` (a directory or an ``.xplane.pb``).

    ``host_spans``: labelled host intervals (start_s, end_s, label) on the
    host's ``perf_counter`` clock, used to name idle gaps;
    ``window_start`` is that clock's reading as the window opened.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    window = None
    annotations: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU") or (
                plane.name.startswith("/device:")
                and "CUSTOM" not in plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for ev in line.events]
                if any(name == WINDOW for _, _, name in events):
                    # the benchmark's own thread: its annotations
                    for a, b, name in events:
                        if name == WINDOW:
                            window = (a, b)
                        elif not name.startswith("$"):
                            annotations.append((a, b, name))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} host annotation")
    lo, hi = window
    busy = 0.0
    module_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    all_ops: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       module_name(ev.name))
                      for ev in (lines["XLA Modules"].events
                                 if "XLA Modules" in lines else ()))
        starts = [m[0] for m in mods]
        ops = []
        for ev in lines["XLA Ops"].events:
            a, b = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if b <= a:
                continue
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = mods[k][2] if k >= 0 and ev.start_ns < mods[k][1] \
                else "(no module)"
            ops.append((a, b, f"{mod}:{op_name(ev.name)}", mod))
        for (key, mod), sec in _self_seconds(ops).items():
            module_s[mod] = module_s.get(mod, 0.0) + sec
            op_s[key] = op_s.get(key, 0.0) + sec
        ops = [(a, b) for a, b, _, _ in ops]
        busy += union_length(ops) * 1e-9
        all_ops.extend(ops)
    n_dev = max(1, min(n_devices, len(devices)) if devices else n_devices)
    labels = [(lo + (a - window_start) * 1e9, lo + (b - window_start) * 1e9,
               name) for a, b, name in host_spans or ()] + [
        (a, b, f"annotation:{name}") for a, b, name in annotations]
    idle: Dict[str, float] = {}
    for a, b in gaps(all_ops, lo, hi):
        mid = 0.5 * (a + b)
        inner = [(e - s, name) for s, e, name in labels if s <= mid <= e]
        label = min(inner)[1] if inner else "host:unlabelled"
        idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy / n_dev,
                     n_devices=n_dev, module_s=module_s, op_s=op_s,
                     idle=sorted(idle.items(), key=lambda kv: -kv[1]))


def telemetry_spans(snapshots, offset: float):
    """(start_s, end_s, label) of the program's telemetry spans on the
    host's ``perf_counter`` clock (``offset`` = clock - recorder time)."""
    out = []
    for snap in snapshots:
        for ev in snap.events:
            if ev["kind"] == "span_end":
                end = ev["t"] + offset
                out.append((end - ev["value"], end, f"span:{ev['name']}"))
    return out
