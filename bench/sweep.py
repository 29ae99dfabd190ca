#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell (not part of a run).

    python3 bench/sweep.py --workload fleet_sf.serve --seed 5 \\
        --rates 20 40 80 --seconds 8

Sets the cell up once, then offers each rate for ``--seconds`` in turn
and prints what was offered and served and the latency percentiles. The
knee is the highest rate whose requests are all served at the offered
rate with no growing backlog; the cell's mix offers 0.8 of it.
"""
import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.harness import NO_DEVICE, Layout, enable_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    enable_cache(Path("."))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return NO_DEVICE
    layout = Layout(Path("."))
    cell = layout.cell(args.workload)
    traffic = layout.traffic(cell["traffic"])
    drv = layout.loop(traffic["loop"]).Loop(
        layout.config(cell["config"]), traffic, args.seed)
    drv.setup()
    for rate in args.rates:
        drv.schedule(rate, args.seconds)
        w = drv.window(args.seconds)
        print(json.dumps({"rate_per_s": rate, **w.end_to_end, **w.stats}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
