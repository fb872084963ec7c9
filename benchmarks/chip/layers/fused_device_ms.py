"""fused_device_ms: per request, the device time of the fused program's
executions (its module is the jitted ``run`` of ``mapping/fused.py``),
from the profiler's trace."""

import readings
import xtrace

MODULE = "jit_run("


def read(run):
    plane = readings.device_plane(run)
    if plane is None or not run.requests:
        return None
    lo, hi = run.window_ns
    ns = xtrace.module_ns(plane, MODULE, lo, hi)
    return ns / 1e6 / run.requests if ns > 0 else None
