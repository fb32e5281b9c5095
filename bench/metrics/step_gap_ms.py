"""Mean host time from one engine step's synced result to the next
step's start, over consecutive steps inside one ``run()`` in the window
(``engine.stats()["host"]``: ``between_s`` over ``betweens``).  The
loop's waits for clients fall outside it."""
from bench import spans


def read(rec):
    got = spans.host_delta(rec, "between_s", "betweens")
    return None if got is None else 1000.0 * got
