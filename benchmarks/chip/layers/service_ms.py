"""service_ms: per request, the ``serve.request`` span less its
``serve.rung`` children: signature, result cache, admission, ladder."""

import readings


def read(run):
    return readings.per_request_ms(
        run, readings.self_seconds(run, "serve.request", "serve.rung"))
