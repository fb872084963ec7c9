"""mapscore_roofline: the least time the scoring kernel's work could
take at the chip's peaks (``work.py``, ``peaks.json``) over the summed
device time of the kernel's executions in the trace, in percent.

One call per request scores the rotation candidates of the request's
sweep: the job's messages in the flat hierarchy, the contracted
cluster graph's in the ``node`` hierarchy (one cluster per router)."""

import re

import numpy as np

import readings
import refmap
import work
import xtrace

# The kernel's ``pallas_call`` has no name of its own yet: its event is
# the custom call that returns the kernel's two (candidates, 8, 128)
# result tiles, f32 and s32.  A name with ``mapscore`` in it is taken
# too, once the program gives it one.
_UNNAMED = re.compile(r"^%\S+ = \(f32\[\d+,8,128\]\S*, "
                      r"s32\[\d+,8,128\]\S*\) custom-call\(")


def is_kernel(name: str) -> bool:
    return "mapscore" in name or bool(_UNNAMED.match(name))


def _coarse_messages(dep) -> int:
    """Messages of the contracted graph: distinct ordered pairs of
    clusters that exchange, one cluster per router."""
    job = dep.job
    nr = -(-job.n // dep.machine.cores_per_router)
    labels = refmap.mj_parts(job.coords, nr)
    ce = labels[job.edges]
    inter = ce[:, 0] != ce[:, 1]
    return len(np.unique(ce[inter, 0] * nr + ce[inter, 1]))


def request_work(run) -> work.Work:
    mix, dep = run.cell.mix, run.deployment
    m = dep.machine
    ncand = len(refmap.rotations(dep.job.coords.shape[1],
                                 len(m.router_dims), int(mix["rotations"])))
    if mix["hierarchy"] == "node":
        messages = _coarse_messages(dep)
    else:
        messages = len(dep.job.edges)
    return work.mapscore_work(
        ncand=ncand, messages=messages, router_dims=m.router_dims,
        wrap=m.wrap, cores_per_node=m.cores_per_router,
        traffic=mix["objective"] == "latency")


def read(run):
    plane = readings.device_plane(run)
    if plane is None or not run.requests:
        return None
    lo, hi = run.window_ns
    ns, count = xtrace.op_ns(plane, is_kernel, lo, hi)
    if ns <= 0:
        return None
    one = request_work(run)
    total = work.Work(one.flops * run.requests, one.bytes * run.requests)
    least, bound = work.least_seconds(total, work.peaks(run.device_kind))
    print(f"mapscore: {count} kernel executions, {ns / 1e9:.6f} s; per "
          f"request {one.flops:.6g} operations, {one.bytes:.6g} bytes; "
          f"bound by {bound}", flush=True)
    return 100.0 * least / (ns / 1e9)
