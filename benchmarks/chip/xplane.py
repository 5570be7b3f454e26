"""Reduction of a JAX profiler trace to the benchmark's device numbers.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps what the reduction needs as plain data (so a recorded trace can be
stored as JSON and the reduction tested on it):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]},
                ...]}

Device planes keep their op line, each op named by its HLO instruction
(``%fusion.12 = ... fusion(...)`` becomes ``fusion.12``); host planes keep
only the benchmark's named spans.  :func:`reduce` then measures, inside the
traced window (the ``bench.window`` span):

* ``busy_s``: the union of the intervals in which an op ran on a device
  (a ``while`` op covers its body), averaged over the devices;
* ``window_s``: the window's length;
* ``device_ops``: the ten ops with the most self time on the device (an
  op's time less that of the ops nested in it);
* ``idle_gaps``: device idle time inside the window, summed by the innermost
  named host span that was open at that moment (``(no span)`` where none
  was), the ten largest.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Iterable

import numpy as np

WINDOW_SPAN = "bench.window"
#: the line of a device plane that holds one event per executed op
OP_LINE = "XLA Ops"
NO_SPAN = "(no span)"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def op_name(hlo: str) -> str:
    """The instruction name of one line of HLO text."""
    return sys.intern(hlo.split(" = ", 1)[0].lstrip("%"))


def load_xplane(log_dir: Path, host_spans: Iterable[str] = ()) -> dict:
    """The newest trace under ``log_dir``, as plain data (see above)."""
    import jax

    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    keep = set(host_spans) | {WINDOW_SPAN}
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    planes = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            if device:
                evs = [[op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                       for e in line.events]
            else:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events if e.name in keep]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray,
                                                          np.ndarray]:
    """Disjoint, sorted intervals covering the same points as the input."""
    if starts.size == 0:
        return starts, ends
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], ends[o]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, dtype=bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, s.size - 1)
    return s[idx], reach[last]


def busy_before(us: np.ndarray, ue: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy time of the disjoint intervals ``(us, ue)`` before each ``t``."""
    cum = np.concatenate([[0], np.cumsum(ue - us)])
    i = np.searchsorted(us, t, side="right") - 1
    inside = np.where(i >= 0, np.clip(t - us[np.maximum(i, 0)], 0,
                                      (ue - us)[np.maximum(i, 0)]), 0)
    return np.where(i >= 0, cum[np.maximum(i, 0)], 0) + inside


def self_times(st: np.ndarray, en: np.ndarray) -> np.ndarray:
    """Each interval's length less that of the intervals nested directly
    in it (ops on one device line nest, as a loop's body in the loop)."""
    own = (en - st).astype(np.float64)
    stack: list[int] = []
    for i in np.lexsort((st - en, st)).tolist():
        s, e = st[i], en[i]
        while stack and en[stack[-1]] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, en[stack[-1]]) - s
        stack.append(i)
    return own


def _innermost_segments(spans: list[tuple[str, int, int]], w0: int, w1: int):
    """Split ``[w0, w1]`` at every span boundary; label each piece with the
    shortest named span that covers it."""
    cuts = sorted({w0, w1} | {t for _, s, e in spans for t in (s, e)
                              if w0 < t < w1})
    by_len = sorted(spans, key=lambda x: x[2] - x[1])
    out = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        mid = (a + b) / 2
        label = next((n for n, s, e in by_len if s <= mid < e), NO_SPAN)
        out.append((label, a, b))
    return out


def reduce(trace: dict, host_spans: Iterable[str], top: int = 10) -> dict:
    names = set(host_spans)
    window, spans, devices = [], [], []
    for plane in trace["planes"]:
        if is_device_plane(plane["name"]):
            evs = [ev for line in plane["lines"] if line["name"] == OP_LINE
                   for ev in line["events"]]
            devices.append(evs)
            continue
        for line in plane["lines"]:
            for n, s, d in line["events"]:
                if n == WINDOW_SPAN:
                    window.append((s, s + d))
                elif n in names:
                    spans.append((n, s, s + d))
    if not window:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    if not devices:
        raise ValueError("trace has no device plane with an op line")
    w0 = min(s for s, _ in window)
    w1 = max(e for _, e in window)
    segments = _innermost_segments(spans, w0, w1)
    seg_a = np.array([a for _, a, _ in segments], dtype=np.int64)
    seg_b = np.array([b for _, _, b in segments], dtype=np.int64)

    busy_ns = 0.0
    op_ns: dict[str, float] = {}
    idle_ns: dict[str, float] = {}
    for evs in devices:
        st = np.array([s for _, s, _ in evs], dtype=np.int64)
        en = st + np.array([d for _, _, d in evs], dtype=np.int64)
        st, en = np.clip(st, w0, w1), np.clip(en, w0, w1)
        us, ue = union(st, en)
        busy_ns += float((ue - us).sum())
        for (n, _, _), v in zip(evs, self_times(st, en)):
            op_ns[n] = op_ns.get(n, 0.0) + float(v)
        idle = (seg_b - seg_a) - (busy_before(us, ue, seg_b)
                                  - busy_before(us, ue, seg_a))
        for (label, _, _), v in zip(segments, idle):
            idle_ns[label] = idle_ns.get(label, 0.0) + float(v)
    nd = len(devices)

    def ranked(d: dict) -> list:
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[n, v / nd / 1e9] for n, v in rows if v > 0]

    return {"busy_s": busy_ns / nd / 1e9, "window_s": (w1 - w0) / 1e9,
            "devices": nd, "device_ops": ranked(op_ns),
            "idle_gaps": ranked(idle_ns)}
