"""expand_ms: per request, the ``pipeline.expand`` spans on the host
clock: each level's expansion back down the hierarchy (the core
expansion at the bottom level, a per-group geometric match above)."""

import readings


def read(run):
    return readings.per_request_ms(
        run, [readings.seconds(s) for s in readings.spans(
            run, "pipeline.expand")])
