#!/usr/bin/env python3
"""Readings that set the correctness limits of a cell (not part of a run).

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \\
        [--seconds 3] [--precs high bf16]

For each seed, in one process: set the cell up, drive a short window at
the cell's own load, and read every number of its check:

* ``program``: what the timed path produced (a sound run; the lower
  reading of a limit is the largest of these over a dozen seeds or more);
* ``control:<prec>``: the reference at ``prec`` ("high": contraction
  operands at 16 mantissa bits, "bf16": at 8) put in the program's place
  (the upper reading is the smallest of these).

Prints one JSON line per reading and a last line with each number's lower
and upper reading. Like ``run.py``, it needs a TPU.
"""
import argparse
import json
import math
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.harness import NO_DEVICE, Layout, enable_cache  # noqa: E402


def readings(layout, workload, seed, seconds, precs):
    cell = layout.cell(workload)
    config = layout.config(cell["config"])
    traffic = layout.traffic(cell["traffic"])
    drv = layout.loop(traffic["loop"]).Loop(config, traffic, seed)
    drv.setup()
    drv.window(seconds)
    drv.release()
    out = {"program": drv.check()}
    for prec in precs:
        drv.control_answers(prec)
        out[f"control:{prec}"] = drv.check()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precs", nargs="*", default=["high", "bf16"])
    args = ap.parse_args(argv)
    enable_cache(Path("."))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return NO_DEVICE
    layout = Layout(Path("."))
    lower, upper = {}, {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        got = readings(layout, args.workload, seed, args.seconds,
                       args.precs)
        for kind, vals in got.items():
            print(json.dumps({"seed": seed, "kind": kind, "values": vals,
                              "s": time.perf_counter() - t0}), flush=True)
            for k, v in vals.items():
                if kind == "program":
                    lower[k] = max(lower.get(k, 0.0), v)
                elif kind.startswith("control:") and math.isfinite(v):
                    upper[k] = min(upper.get(k, math.inf), v)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": {k: v for k, v in upper.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
