"""map_s: seconds per cold mapping request, the window's serving time
over the requests completed in it (host clock)."""


def read(run):
    if not run.requests:
        return None
    return sum(run.latencies_s) / run.requests
