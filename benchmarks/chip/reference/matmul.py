"""Plain reference of the systolic GEMM: a batched integer matmul.

``C = A @ B`` per lane, computed exactly in int64 and wrapped to the
configuration's datapath width (int32 for the paper's GEMM), which is what
the hardware's int32 accumulators hold.  ``dtype`` computes it in another
integer precision instead; the benchmark's control passes one narrower
than the datapath.
"""

from __future__ import annotations

import numpy as np


def wrap(x: np.ndarray, bits: int) -> np.ndarray:
    """Two's-complement wrap of int64 values to ``bits`` bits."""
    half = np.int64(1) << np.int64(bits - 1)
    return ((x + half) & ((half << np.int64(1)) - np.int64(1))) - half


def reference(config: dict, args: list[np.ndarray],
              dtype=np.int64) -> np.ndarray:
    a, b = args
    c = np.matmul(a.astype(dtype), b.astype(dtype)).astype(np.int64)
    return wrap(c, int(config["datapath_bits"]))
