"""pipeline_host_ms: per request, the ``pipeline.map`` span less its
``pipeline.fused`` child: the pipeline's and hierarchy's host work."""

import readings


def read(run):
    return readings.per_request_ms(
        run, readings.self_seconds(run, "pipeline.map", "pipeline.fused"))
