"""coarsen_device_ms: per request, the device time of the partition
engine's own programs (modules ``jit_partition_mj`` and
``jit_partition_hilbert`` of ``core/partition_jax.py``), from the
profiler's trace.  They run outside the fused program only where the
hierarchy coarsens the job: coarsening's one engine call a level."""

import readings
import xtrace

MODULE = "jit_partition_"


def read(run):
    plane = readings.device_plane(run)
    if plane is None or not run.requests:
        return None
    lo, hi = run.window_ns
    ns = xtrace.module_ns(plane, MODULE, lo, hi)
    return ns / 1e6 / run.requests if ns > 0 else None
