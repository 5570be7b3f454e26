"""The program's own spans, for the per-layer metrics that read them.

The program times its regions with ``repro.core.trace``: spans named
``hir.*`` and counters, kept while a recorder is active.  The harness loads
the per-layer readers only for a ``--trace 1`` run, while it reads the cell
and before set-up.  Each reader that reads spans calls :func:`start` as it
is loaded, so such a run records its set-up, window, traced slice and
check, and a ``--trace 0`` run records nothing.  Under a program without
``repro.core.trace`` nothing is recorded and the readers return None.

:func:`seconds_per_unit` finds the window's units among the recorded calls.
Each unit made one top-level call of the program (``ROOT``: ``hir.sim.run``
for a bulk batch, ``hir.diff`` for a DSE design), and the first
``hir.sim.scan`` span inside that call lasted exactly the unit's ``run_s``,
which the program takes from that span.  The window is the run of ``N``
consecutive calls, ``N`` its units, whose scans match the units' ``run_s``
in order.
"""

from __future__ import annotations

import bisect
from typing import Optional

#: each runner's unit makes one top-level call of this name
ROOT = {"bulk": "hir.sim.run", "dse": "hir.diff"}

_block = None
_recorder = None


def start() -> None:
    """Record from now on into a fresh recorder, if the program has one."""
    global _block, _recorder
    stop()
    try:
        from repro.core import trace
    except ImportError:
        return
    _block = trace.record()
    _recorder = _block.__enter__()


def stop() -> None:
    """End the recording :func:`start` began, if any."""
    global _block, _recorder
    if _block is not None:
        _block.__exit__(None, None, None)
    _block = _recorder = None


def recorder():
    """The active recorder of :func:`start`, or None."""
    return _recorder


def _scan_s(call: list) -> Optional[float]:
    for name, _parent, s, e in call:
        if name == "hir.sim.scan":
            return (e - s) / 1e9
    return None


def unit_calls(run) -> Optional[list[list[tuple]]]:
    """For each unit of the window, the spans of its top-level call (the
    call's own span included), in start order; None where the spans were
    not recorded."""
    root = ROOT.get(run.runner)
    if _recorder is None or root is None or not run.units:
        return None
    spans = sorted(_recorder.spans, key=lambda sp: (sp[2], -sp[3]))
    starts = [sp[2] for sp in spans]
    calls = []
    for sp in spans:
        if sp[0] == root and sp[1] is None:
            lo = bisect.bisect_left(starts, sp[2])
            hi = bisect.bisect_right(starts, sp[3])
            calls.append([c for c in spans[lo:hi] if c[3] <= sp[3]])
    scans = [_scan_s(c) for c in calls]
    want = [u["run_s"] for u in run.units]
    n = len(want)
    for i in range(len(calls) - n, -1, -1):
        if scans[i:i + n] == want:
            return calls[i:i + n]
    return None


def seconds_per_unit(run, runner: str, *names: str) -> Optional[float]:
    """Mean seconds per unit of the window spent in the spans ``names``,
    in a run of ``runner``; None in another runner's run or where the
    spans were not recorded."""
    if run.runner != runner:
        return None
    calls = unit_calls(run)
    if calls is None:
        return None
    total = sum(e - s for call in calls for n, _p, s, e in call
                if n in names)
    return total / 1e9 / len(calls)
