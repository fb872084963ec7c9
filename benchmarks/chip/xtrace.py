"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is held as plain data: planes, each with lines, each line its
events as arrays (name index, start, duration), all on one clock.
:func:`load` reads the ``.xplane.pb`` file that ``jax.profiler`` writes
into that form, keeping the device planes whole and of the host only
the window's annotation; the tests build one by hand with
:meth:`Line.of`.  Everything else here is arithmetic on intervals, the
same in every run:

- the device's busy time is the union of the intervals in which an
  operation ran on it, clipped to the traced window; its idle share is
  one minus busy over the window;
- a module's (jitted program's) device time is the sum of its
  executions' durations;
- the operations that took most time are counted by their leaf events
  (a loop's event holds its body's, which are counted instead);
- each idle gap of the device is named by what the host was doing in
  it: the innermost host span open at the gap's midpoint.
"""

from __future__ import annotations

import array
import dataclasses
import glob
import os
import re
import typing

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
NAME_CHARS = 160  # an operation's name in the breakdown is cut to this


class Event(typing.NamedTuple):
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass(frozen=True)
class Line:
    name: str
    names: tuple        # the distinct event names
    idx: np.ndarray     # (n,) each event's index into ``names``
    start: np.ndarray   # (n,) float64 ns
    dur: np.ndarray     # (n,) float64 ns

    @property
    def end(self) -> np.ndarray:
        return self.start + self.dur

    @classmethod
    def of(cls, name: str, events) -> "Line":
        """A line from ``(name, start_ns, duration_ns)`` events."""
        names: dict = {}
        idx, start, dur = array.array("l"), array.array("d"), \
            array.array("d")
        for ev_name, s, d in events:
            idx.append(names.setdefault(ev_name, len(names)))
            start.append(s)
            dur.append(d)
        return cls(name, tuple(names), np.asarray(idx, dtype=np.int64),
                   np.asarray(start, dtype=np.float64),
                   np.asarray(dur, dtype=np.float64))

    def matching(self, match) -> np.ndarray:
        """Mask of the events whose name satisfies ``match``."""
        hit = np.array([bool(match(n)) for n in self.names], dtype=bool)
        return hit[self.idx] if len(self.idx) else np.zeros(0, bool)


@dataclasses.dataclass(frozen=True)
class Plane:
    name: str
    lines: tuple

    def line(self, name: str):
        for ln in self.lines:
            if ln.name == name:
                return ln
        return None


def is_device(plane_name: str) -> bool:
    """An accelerator's plane: not the host's, nor a custom plane such
    as ``/device:CUSTOM:Megascale Trace``."""
    return re.fullmatch(r"/device:(TPU|GPU):\d+", plane_name) is not None


def load(logdir: str, host_events=(WINDOW,)) -> list:
    """The planes of the newest ``.xplane.pb`` under ``logdir``: device
    planes whole, host planes with only the events named in
    ``host_events``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    keep = set(host_events)
    planes = []
    for p in data.planes:
        device = is_device(p.name)
        lines = []
        for ln in p.lines:
            evs = ((e.name, e.start_ns, e.duration_ns) for e in ln.events)
            if not device:
                evs = [ev for ev in evs if ev[0] in keep]
            lines.append(Line.of(ln.name, evs))
        planes.append(Plane(p.name, tuple(lines)))
    return planes


def device_planes(planes) -> list:
    return [p for p in planes if is_device(p.name)]


def window(planes, name: str = WINDOW):
    """``(start_ns, end_ns)`` of the host annotation ``name``."""
    for p in planes:
        if is_device(p.name):
            continue
        for ln in p.lines:
            hit = np.flatnonzero(ln.matching(lambda n: n == name))
            if len(hit):
                i = hit[0]
                return float(ln.start[i]), float(ln.end[i])
    raise LookupError(f"no host event {name!r} in the trace")


def check_complete(planes, window_ns, request_s: float) -> None:
    """Raise where a device's operations stop more than one request
    (``request_s`` seconds) before the window ends: the profiler then
    dropped events, and every device number would read low."""
    lo, hi = window_ns
    for p in device_planes(planes):
        ln = _ops(p)
        end = ln.end
        last = float(end[end <= hi].max()) if (end <= hi).any() else lo
        if last < hi - request_s * 1e9:
            raise RuntimeError(
                f"the trace of {p.name} ends {(hi - last) / 1e9:.3f} s "
                f"before the window does: the profiler dropped events")


def _ops(plane: Plane):
    ln = plane.line(OPS_LINE)
    return ln if ln is not None else Line.of(OPS_LINE, ())


def _clipped(ln: Line, lo: float, hi: float) -> np.ndarray:
    """Each event's nanoseconds inside ``[lo, hi)`` (0 when outside)."""
    return np.maximum(np.minimum(ln.end, hi) - np.maximum(ln.start, lo),
                      0.0)


def union(start, end, lo: float, hi: float) -> list:
    """Sorted, merged ``[start, end)`` intervals clipped to
    ``[lo, hi)``."""
    s = np.maximum(np.asarray(start, dtype=np.float64), lo)
    e = np.minimum(np.asarray(end, dtype=np.float64), hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return []
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(s) - 1]
    return list(zip(s[first].tolist(), reach[last].tolist()))


def busy_ns(plane: Plane, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi)`` in which an operation ran."""
    ln = _ops(plane)
    return float(sum(e - s for s, e in union(ln.start, ln.end, lo, hi)))


def gaps(plane: Plane, lo: float, hi: float) -> list:
    """The idle ``[start, end)`` intervals of ``[lo, hi)``."""
    ln = _ops(plane)
    out, t = [], lo
    for s, e in union(ln.start, ln.end, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def module_ns(plane: Plane, prefix: str, lo: float, hi: float) -> float:
    """Summed device time of the executions of modules whose name starts
    with ``prefix``, within ``[lo, hi)``."""
    ln = plane.line(MODULES_LINE)
    if ln is None:
        return 0.0
    sel = ln.matching(lambda n: n.startswith(prefix))
    return float(_clipped(ln, lo, hi)[sel].sum())


def op_ns(plane: Plane, match, lo: float, hi: float) -> tuple:
    """``(summed ns, count)`` of the operations whose name satisfies
    ``match``, within ``[lo, hi)``."""
    ln = _ops(plane)
    t = _clipped(ln, lo, hi)
    sel = ln.matching(match) & (t > 0)
    return float(t[sel].sum()), int(sel.sum())


def leaves(ln: Line) -> np.ndarray:
    """Mask of the events that hold no other event of the line (events
    of one line nest: a loop's event spans its body's)."""
    n = len(ln.start)
    if not n:
        return np.zeros(0, dtype=bool)
    order = np.lexsort((-ln.dur, ln.start))  # a parent before its child
    s, e = ln.start[order], ln.end[order]
    leaf = np.ones(n, dtype=bool)
    leaf[:-1] = s[1:] >= e[:-1]
    out = np.empty(n, dtype=bool)
    out[order] = leaf
    return out


def top_ops(plane: Plane, lo: float, hi: float, n: int = 10) -> list:
    """``[name, seconds]`` of the ``n`` operations whose leaf events
    took most time."""
    ln = _ops(plane)
    t = _clipped(ln, lo, hi)
    sel = leaves(ln) & (t > 0)
    tot = np.bincount(ln.idx[sel], weights=t[sel],
                      minlength=len(ln.names))
    best = np.argsort(-tot, kind="stable")[:n]
    return [[ln.names[i][:NAME_CHARS], float(tot[i]) / 1e9]
            for i in best if tot[i] > 0]


def attribute(gap_list, spans, idle_name: str = "outside any span") -> dict:
    """Seconds of idle gaps by the innermost host span open at each
    gap's midpoint.  ``spans`` are ``(name, start_ns, end_ns)`` on the
    trace's clock; the innermost is the latest to start."""
    if not gap_list:
        return {}
    g = np.asarray(gap_list, dtype=np.float64)
    mid = (g[:, 0] + g[:, 1]) / 2
    order = np.argsort(mid, kind="stable")
    smid = mid[order]
    owner = np.full(len(g), -1, dtype=np.int64)
    names = []
    for name, a, b in sorted(spans, key=lambda s: s[1]):
        i, j = np.searchsorted(smid, [a, b], side="left")
        if j > i:  # later starts overwrite: the innermost wins
            owner[order[i:j]] = len(names)
        names.append(name)
    out: dict = {}
    for k, secs in zip(owner.tolist(), ((g[:, 1] - g[:, 0]) / 1e9).tolist()):
        key = names[k] if k >= 0 else idle_name
        out[key] = out.get(key, 0.0) + secs
    return out
