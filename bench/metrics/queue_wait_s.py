"""Mean wait of a request in the engine's queue, from its submission to
its admission into a slot, over the admissions in the window
(``engine.stats()["host"]``: ``queue_s`` over ``admitted``).  With one
prompt token a step, it grows with every earlier admission's prefill."""
from bench import spans


def read(rec):
    return spans.host_delta(rec, "queue_s", "admitted")
