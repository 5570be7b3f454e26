"""Plain reference of the histogram: pixels per bin per lane.

``out[lane, b]`` counts the pixels of the lane's ``Img`` equal to ``b``, for
the configuration's ``build.bins`` bins: ``np.bincount`` of each lane.  The
counts never pass the tile's pixel count (8,192), so any integer precision
of 16 bits or more gives the same answer; ``dtype`` only casts it.  Hence
:func:`control`, the wrong answer of the design's realistic fault (the bin
read-modify-write scheduled at II=1), which the benchmark's control uses in
place of a narrower precision.
"""

from __future__ import annotations

import numpy as np


def _count(img: np.ndarray, bins: int, kept: np.ndarray) -> np.ndarray:
    """Per lane, the pixels where ``kept`` holds, counted per bin."""
    lanes = img.shape[0]
    flat = (np.arange(lanes, dtype=np.int64)[:, None] * bins + img)[kept]
    return np.bincount(flat, minlength=lanes * bins).reshape(lanes, bins)


def reference(config: dict, args: list[np.ndarray],
              dtype=np.int64) -> np.ndarray:
    (img,) = args
    counts = _count(img, int(config["build"]["bins"]),
                    np.ones(img.shape, dtype=bool))
    return counts.astype(dtype).astype(np.int64)


def control(config: dict, args: list[np.ndarray]) -> np.ndarray:
    """The answer of an II=1 schedule without forwarding.  A pixel reads
    its bin in the cycle in which the pixel before it writes back, so it
    misses that pixel's update when both fall in one bin.  In a run of
    ``L`` equal pixels every second update is lost: the run adds
    ``ceil(L / 2)``, and a lane differs from the reference exactly where
    two consecutive pixels are equal."""
    (img,) = args
    n = img.shape[1]
    starts = np.ones(img.shape, dtype=bool)
    starts[:, 1:] = img[:, 1:] != img[:, :-1]
    pos = np.broadcast_to(np.arange(n), img.shape)
    offset = pos - np.maximum.accumulate(np.where(starts, pos, 0), axis=1)
    return _count(img, int(config["build"]["bins"]), offset % 2 == 0)
