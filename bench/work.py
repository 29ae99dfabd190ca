"""The work an estimate needs, counted from shapes, and the chip's peaks.

These count what the problem requires, not what the current kernels do:
true degrees (no padding), one read of each operand, a symmetric
curvature Gram, and a sparse pseudo-score. A change that removes padding
or swaps a kernel is then read against the same work. A roofline share is
the least time the chip could take for that work (operations over peak
rate or bytes over peak bandwidth, whichever is larger) over the measured
device time, so it cannot pass 100% while the device time covers the work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Sequence, Tuple

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
#: per-sample, per-channel operations of the residual and curvature
#: epilogue (a sigmoid, a product and a few adds)
EPILOGUE_OPS = 8


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table {path} has {sorted(table)}")
    return table[device_kind]


def bucket_solve_work(degrees: Sequence[int], iters: Sequence[int], n: int,
                      C: int = 1, want_influence: bool = False,
                      itemsize: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of the local Newton solves of nodes with true
    ``degrees``, each run for ``iters`` Newton iterations on n samples,
    plus the final sandwich pass (score matrix, curvature, J, and the
    per-sample influence when ``want_influence``).

    One iteration of a node with d = (deg + 1) C coordinates reads its
    deg + 1 sample columns once (bytes) and computes eta (2 n d), the
    epilogue, the score (2 n d) and the symmetric curvature Gram
    (n d (d + 1)).
    """
    deg = np.asarray(degrees, dtype=np.float64)
    it = np.asarray(iters, dtype=np.float64)
    d = (deg + 1.0) * C
    cols = (deg + 1.0) * n * itemsize
    per_iter = n * (4.0 * d + d * (d + 1.0) + EPILOGUE_OPS * C)
    final = n * (4.0 * d + 2.0 * d * (d + 1.0) + EPILOGUE_OPS * C)
    final_bytes = cols.copy()
    if want_influence:
        final = final + 2.0 * n * d * d
        final_bytes = final_bytes + n * d * itemsize
    flops = float(np.sum(it * per_iter + final))
    nbytes = float(np.sum(it * cols + final_bytes))
    return flops, nbytes


def score_work(n: int, p: int, m: int, C: int = 1,
               itemsize: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of the sparse pseudo-score of n samples: one
    read of the (n, p) samples, and per sample and channel 2 (p + 2m)
    operations for the conditional logits plus as many for the gradient."""
    return 4.0 * n * C * (p + 2.0 * m), float(n * p * C * itemsize)


def roofline_pct(flops: float, nbytes: float, device_s: float,
                 peak: dict) -> Tuple[float, str]:
    """(share of the roofline in %, the bound that sets it)."""
    t_compute = flops / float(peak["flops_per_s"])
    t_memory = nbytes / float(peak["hbm_bytes_per_s"])
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / device_s, bound
