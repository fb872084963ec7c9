"""setup_s: seconds from the start of the process to the end of the
warm-up: interpreter and JAX start, the deployment built, the compile
cache read or filled, the warm-up requests served (host clock)."""


def read(run):
    return run.setup_s
