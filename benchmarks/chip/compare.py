"""The comparison that decides ``correct``: outputs against the reference."""

from __future__ import annotations

import numpy as np


def mismatched_lanes(got: np.ndarray, want: np.ndarray) -> int:
    """Lanes (leading axis) whose output differs from the reference in any
    word; every lane counts when the shapes differ."""
    if got.shape != want.shape:
        return int(want.shape[0])
    lanes = want.shape[0]
    return int((got.reshape(lanes, -1) != want.reshape(lanes, -1))
               .any(axis=1).sum())
