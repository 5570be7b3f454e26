"""Program spans and counters, on the profiler's clock.

``span(name)`` times a region of the compiler or the simulator.  It opens a
``jax.profiler.TraceAnnotation`` of the same name, so under any
``jax.profiler`` trace the program's regions (all named ``hir.*``) appear on
the host line beside the device's ops.  ``count(name, n)`` adds to a
counter.  Both are kept only while ``record()`` is active::

    from repro.core import trace

    with trace.record() as rec:
        run_differential(...)
    rec.spans      # [(name, parent, start_ns, end_ns), ...] in closing order
    rec.counters   # {name: total}
    rec.totals()   # {name: {"s": seconds, "n": spans}}

Outside ``record()`` a span costs one annotation and one check, and a
counter one check; nothing is kept and nothing is written anywhere.  A
span's own duration is always taken (``span.seconds`` after the block), so
callers that report a time (``SimResult.run_s``, ``PassStatistics.wall_s``,
``generate_verilog(timings=)``) read it from the span and each region has
one timer.  Spans nest by the Python call stack; ``parent`` is the name of
the innermost span open when the span began (``None`` at the top, or when
it began before recording did).  Recording is per process: spans opened in
pool workers are not recorded.
"""

from __future__ import annotations

import contextlib
import functools
import sys
import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

#: recorders of the ``record()`` blocks now open, outermost first
_active: list["Recorder"] = []
#: names of the recorded spans now open, innermost last
_open: list[str] = []
_annotation = None


@dataclass(eq=False)
class Recorder:
    """What one ``record()`` block kept."""

    spans: list[tuple[str, Optional[str], int, int]] = field(
        default_factory=list)
    counters: dict[str, int] = field(default_factory=dict)

    def totals(self) -> dict[str, dict]:
        """Seconds and number of spans per name."""
        out: dict[str, dict] = {}
        for name, _parent, start, end in self.spans:
            t = out.setdefault(name, {"s": 0.0, "n": 0})
            t["s"] += (end - start) / 1e9
            t["n"] += 1
        return out


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once JAX is imported; before that
    no profiler can be running, and the program does not import JAX for
    it."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class span:
    """``with span("hir.x") as s: ...``; ``s.seconds`` is its duration.
    ``@span("hir.x")`` puts every call of a function in such a span."""

    __slots__ = ("name", "start_ns", "end_ns", "_note", "_parent", "_kept")

    def __init__(self, name: str):
        self.name = name
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "span":
        ta = _trace_annotation()
        self._note = ta(self.name) if ta is not None else None
        if self._note is not None:
            self._note.__enter__()
        self._kept = bool(_active)
        if self._kept:
            self._parent = _open[-1] if _open else None
            _open.append(self.name)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._kept:
            _open.pop()
            rec = (self.name, self._parent, self.start_ns, self.end_ns)
            for r in _active:
                r.spans.append(rec)
        if self._note is not None:
            self._note.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __call__(self, fn):
        """As a decorator: every call of ``fn`` runs in a span of this
        name."""
        name = self.name

        @functools.wraps(fn)
        def spanned(*args, **kw):
            with span(name):
                return fn(*args, **kw)

        return spanned


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` of every active recorder."""
    for r in _active:
        r.counters[name] = r.counters.get(name, 0) + n


@contextlib.contextmanager
def record() -> Iterator[Recorder]:
    """Keep the spans and counters of the block in a fresh ``Recorder``.
    Blocks may nest; each open recorder keeps everything of its block."""
    rec = Recorder()
    _active.append(rec)
    try:
        yield rec
    finally:
        _active.remove(rec)


__all__ = ["Recorder", "count", "record", "span"]
