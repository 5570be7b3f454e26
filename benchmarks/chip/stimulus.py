"""Seeded stimulus for the benchmark's designs, made in bulk on the host.

A configuration lists its function's arguments under ``inputs``: a
``shape`` and either a half-open integer domain ``[low, high)`` (the domain
the gallery's own ``make_inputs`` draws from) or a constant ``fill`` (an
output memref starts zeroed).  :func:`batch` draws ``lanes`` stimulus
vectors at once.  Unit ``index`` of a run with seed ``seed`` always gets the
same vectors, so the check after the window makes them again instead of
keeping them, and every seed gives the same sizes.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, index: int) -> np.random.Generator:
    """The stream of unit ``index`` under ``seed`` (any integers)."""
    return np.random.default_rng([seed % (1 << 64), index % (1 << 64)])


def batch(inputs: list[dict], lanes: int, seed: int,
          index: int) -> list[np.ndarray]:
    """One batch-first int64 array per argument: ``(lanes, *shape)``."""
    g = rng(seed, index)
    out = []
    for spec in inputs:
        shape = (lanes, *spec["shape"])
        if "fill" in spec:
            out.append(np.full(shape, spec["fill"], dtype=np.int64))
        else:
            out.append(g.integers(spec["low"], spec["high"], size=shape,
                                  dtype=np.int64))
    return out


def domain_args(inputs: list[dict],
                args: list[np.ndarray]) -> list[np.ndarray]:
    """The drawn (not constant) arguments: what the reference reads."""
    return [a for spec, a in zip(inputs, args) if "fill" not in spec]
