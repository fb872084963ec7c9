"""contract_ms: per request, the ``pipeline.contract`` spans on the host
clock: coarsening's contraction of the job after its labels (cluster
sizes, weights, centroids and the contracted edge list)."""

import readings


def read(run):
    return readings.per_request_ms(
        run, [readings.seconds(s) for s in readings.spans(
            run, "pipeline.contract")])
