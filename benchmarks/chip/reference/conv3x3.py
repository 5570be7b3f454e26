"""Plain reference of the streaming convolution: a batched 3x3 valid
convolution (correlation, as the paper's design computes it) with the
configuration's constant ``weights``.

``out[r, c] = sum_{i,j} W[i][j] * img[r + i, c + j]`` per lane, computed in
int64 and wrapped to the datapath width.  ``dtype`` computes it in another
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
    (img,) = args
    img = img.astype(dtype)
    _, h, w = img.shape
    out = np.zeros((img.shape[0], h - 2, w - 2), dtype=dtype)
    for i, row in enumerate(config["weights"]):
        for j, wt in enumerate(row):
            out += dtype(wt) * img[:, i:h - 2 + i, j:w - 2 + j]
    return wrap(out.astype(np.int64), int(config["datapath_bits"]))
