"""fused_ms: per request, the ``pipeline.fused`` span on the host clock:
the fused program's preparation, upload, device run and read-back."""

import readings


def read(run):
    return readings.per_request_ms(
        run, [readings.seconds(s) for s in readings.spans(
            run, "pipeline.fused")])
