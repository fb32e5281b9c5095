"""Mean wait from a request's admission into a slot to its first
generated token, over the first tokens in the window
(``engine.stats()["host"]``: ``first_token_wait_s`` over
``first_tokens``).  It holds the request's own prefill and those of the
slots admitted after it before the next decode round."""
from bench import spans


def read(rec):
    return spans.host_delta(rec, "first_token_wait_s", "first_tokens")
