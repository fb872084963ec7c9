"""Helpers the metric readers share: spans of the window, the device's
trace, and the breakdown of a traced run."""

from __future__ import annotations

import xtrace


def spans(run, name: str) -> list:
    return [s for s in run.spans if s["name"] == name]


def seconds(s: dict) -> float:
    return (s["end_ns"] - s["start_ns"]) / 1e9


def self_seconds(run, name: str, child: str) -> list:
    """Per span ``name``: its duration less that of its direct children
    named ``child``."""
    kids: dict = {}
    for s in spans(run, child):
        kids[s["parent"]] = kids.get(s["parent"], 0.0) + seconds(s)
    return [seconds(s) - kids.get(s["id"], 0.0) for s in spans(run, name)]


def per_request_ms(run, values) -> float | None:
    if not run.requests or not values:
        return None
    return 1e3 * sum(values) / run.requests


def device_plane(run):
    """The traced device plane (the cell's one chip), or None."""
    if run.planes is None:
        return None
    planes = xtrace.device_planes(run.planes)
    return planes[0] if planes else None


def busy_window(run) -> dict:
    plane = device_plane(run)
    lo, hi = run.window_ns
    busy = xtrace.busy_ns(plane, lo, hi) if plane is not None else 0.0
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9}


def breakdown(run) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by the innermost host span open in them."""
    plane = device_plane(run)
    if plane is None:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = run.window_ns
    host = [(s["name"], s["start_ns"], s["end_ns"]) for s in run.spans]
    by_span = xtrace.attribute(xtrace.gaps(plane, lo, hi), host)
    idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": xtrace.top_ops(plane, lo, hi),
            "idle_gaps": [[k, v] for k, v in idle]}
