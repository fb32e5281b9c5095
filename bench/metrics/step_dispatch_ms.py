"""Mean host time from an engine step's start to the return of its
jitted call (``serve.dispatch``: the stall checks, the positions and
tokens copied to the device, the dispatch), over the window's steps,
from ``engine.stats()["host"]`` at the window's edges."""
from bench import spans


def read(rec):
    got = spans.host_delta(rec, "dispatch_s", "steps")
    return None if got is None else 1000.0 * got
