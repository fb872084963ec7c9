"""fused_host_ms: per request, the ``pipeline.fused`` span less its
``fused.execute`` child on the host clock: the fused program's host
preparation (the partition engine's inputs, padding, the uploads made
before the call) and the building of its answer, without the program's
call, run and the reads of its outputs."""

import readings


def read(run):
    if not readings.spans(run, "fused.execute"):
        return None
    return readings.per_request_ms(
        run, readings.self_seconds(run, "pipeline.fused", "fused.execute"))
